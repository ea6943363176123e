"""Operations and bytes of each layer of the net ``resnet`` (convs with
batch norm and residual adds, a padded max-pool, a global average pool
and an FC layer), from the configuration file's ``layers`` table alone.
The counts and ``LayerWork`` are the harness's (``bench/work.py``).

A conv's weights are its filters and the bias its batch norm folds into;
a conv that adds a shortcut reads that tensor once, counted in its
``in_bytes``.  The pools are no layers here: they do no multiply-adds.
"""
from __future__ import annotations

from work import DTYPE_BYTES, LayerWork, conv_macs


def layer_work(cfg: dict) -> list:
    """Per-image :class:`LayerWork` of every conv and FC layer."""
    size = DTYPE_BYTES[cfg["dtype"]]
    hw = {"image": (cfg["image_size"], cfg["in_channels"])}
    out = []
    for layer in cfg["layers"]:
        h, c = hw[layer["input"]]
        if layer["op"] in ("conv", "maxpool"):
            k = layer["kernel"] if layer["op"] == "conv" else layer["window"]
            lo, hi = layer["padding"]
            ho = (h + lo + hi - k) // layer["stride"] + 1
            co = layer.get("out", c)
            if layer["op"] == "conv":
                direct, least = conv_macs(ho, ho, c, co, k, layer["stride"])
                shortcut = ho * ho * co if layer["add"] else 0
                out.append(LayerWork(
                    layer["name"], "conv", direct, least,
                    (h * h * c + shortcut) * size, ho * ho * co * size,
                    (k * k * c * co + co) * size))
            hw[layer["name"]] = (ho, co)
        elif layer["op"] == "avgpool":
            hw[layer["name"]] = (1, c)
        else:
            macs = c * layer["out"]
            out.append(LayerWork(layer["name"], "fc", macs, macs, c * size,
                                 layer["out"] * size,
                                 (macs + layer["out"]) * size))
            hw[layer["name"]] = (1, layer["out"])
    return out
