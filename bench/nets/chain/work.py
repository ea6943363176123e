"""Operations and bytes of each layer of the net ``chain`` (conv layers
with fused ReLU/LRN/max-pool, then FC layers), from the configuration
file's ``layers`` table alone.  The counts and ``LayerWork`` are the
harness's (``bench/work.py``)."""
from __future__ import annotations

from work import DTYPE_BYTES, LayerWork, conv_macs, conv_out


def pool_out(h: int, window: int, stride: int) -> int:
    """VALID max-pool extent."""
    return (h - window) // stride + 1


def layer_work(cfg: dict) -> list:
    """Per-image :class:`LayerWork` of every layer of a configuration."""
    size = DTYPE_BYTES[cfg["dtype"]]
    h, c = cfg["image_size"], cfg["in_channels"]
    flat = None
    out = []
    for layer in cfg["layers"]:
        if layer["op"] == "conv":
            k, s, g = layer["kernel"], layer["stride"], layer["groups"]
            ho = conv_out(h, k, s, layer["padding"])
            direct, least = conv_macs(ho, ho, c // g, layer["out"], k, s)
            hp = pool_out(ho, *layer["pool"]) if layer["pool"] else ho
            out.append(LayerWork(
                layer["name"], "conv", direct, least,
                h * h * c * size, hp * hp * layer["out"] * size,
                (k * k * (c // g) * layer["out"] + layer["out"]) * size))
            h, c = hp, layer["out"]
        else:
            d_in = flat if flat is not None else h * h * c
            macs = d_in * layer["out"]
            out.append(LayerWork(
                layer["name"], "fc", macs, macs, d_in * size,
                layer["out"] * size, (macs + layer["out"]) * size))
            flat = layer["out"]
    return out
