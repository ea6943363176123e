"""Plain reference of ResNet-50 v1.5 (``models/resnet.py``): the forward
pass in straightforward ``jax.numpy`` at float32, under
``jax.default_matmul_precision("highest")``.

No kernels, no packing, no folding and no batching logic: each conv is
``lax.conv_general_dilated`` with explicit symmetric padding
(``(k - 1) // 2`` on every side), followed by inference batch norm
applied unfolded, (y - mean) / sqrt(var + eps) * gamma + beta; the
max-pool is ``lax.reduce_window`` with one -inf pad on every side; the
head is a mean over H and W and a dense FC.  It reads the model's
parameter tree (``models/resnet.py::init``) and its config's sizes.

Departure from the published model (He et al. 2015, Table 1, 50-layer;
v1.5 as in torchvision's ``resnet50``): inference only — batch norm uses
its running statistics, and there is no dropout (the model has none).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _conv(x, w, stride: int):
    k = w.shape[0]
    pad = (k - 1) // 2
    return jax.lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(y, bn, eps: float):
    f32 = jnp.float32
    return ((y - bn["mean"].astype(f32))
            / jnp.sqrt(bn["var"].astype(f32) + eps)
            * bn["gamma"].astype(f32) + bn["beta"].astype(f32))


def _conv_bn(x, p, stride: int, eps: float):
    return _bn(_conv(x, p["w"], stride), p["bn"], eps)


def forward(params, cfg, images):
    """Logits (B, classes) in float32 for images (B, H, W, C)."""
    eps = cfg.bn_eps
    relu = jax.nn.relu
    with jax.default_matmul_precision("highest"):
        x = relu(_conv_bn(images.astype(jnp.float32), params["stem"], 2,
                          eps))
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1),
                                  ((0, 0), (1, 1), (1, 1), (0, 0)))
        for si, n in enumerate(cfg.stage_blocks):
            for bi in range(n):
                blk = f"s{si + 2}b{bi + 1}"
                stride = 2 if bi == 0 and si > 0 else 1
                y = relu(_conv_bn(x, params[f"{blk}_c1"], 1, eps))
                y = relu(_conv_bn(y, params[f"{blk}_c2"], stride, eps))
                y = _conv_bn(y, params[f"{blk}_c3"], 1, eps)
                short = (_conv_bn(x, params[f"{blk}_proj"], stride, eps)
                         if bi == 0 else x)
                x = relu(y + short)
        feats = x.mean(axis=(1, 2))
        fc = params["fc"]
        return feats @ fc["w"].astype(jnp.float32) + fc["b"].astype(
            jnp.float32)
