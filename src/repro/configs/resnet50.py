"""ResNet-50 v1.5 — He et al. 2015 (arXiv:1512.03385, Table 1, 50-layer),
with the stride of each down-sampling bottleneck on its 3x3 conv, as
torchvision's ``resnet50`` and MLPerf Inference's image classification
model have it (``models/resnet.py``)."""
from repro.models.resnet import ResNetConfig


def config() -> ResNetConfig:
    return ResNetConfig()
