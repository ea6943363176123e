from . import lm  # noqa: F401


def model_for(cfg):
    """Dispatch to the model family implementation."""
    if cfg.family == "cnn":
        if cfg.arch == "resnet":
            from . import resnet
            return resnet
        from . import alexnet
        return alexnet
    if cfg.family == "audio":
        from . import encdec
        return encdec
    if cfg.family == "vlm":
        from . import vlm
        return vlm
    return lm
