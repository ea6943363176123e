"""Operations and bytes of each layer, from its shapes alone.

The yardstick for ``model.mfu`` and ``conv_roofline``.  It reads a
configuration file's layer table (``bench/configs/<name>.json``) and
nothing of the program, so it stays the same whichever kernel a later
change runs a layer on.

* Operations are 2 x the fewest multiply-adds any route of this datapath
  needs: the Winograd F(4,3) count for 3x3 stride-1 convs (36 products per
  4x4 output tile and channel pair, edge tiles whole), the direct count for
  every other conv, and the dense count for FC layers.  No kernel of the
  program does fewer, so a share of it cannot pass 100% honestly.
* Bytes are the layer's input, output and weights, each moved once, in the
  configuration's dtype.  A conv layer's output is what it writes: after
  its fused pool.
"""
from __future__ import annotations

from dataclasses import dataclass

_DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}

# Winograd F(m, r): each m x m output tile costs (m + r - 1)^2 products
WINOGRAD_M = 4


@dataclass(frozen=True)
class LayerWork:
    """One layer for one image: multiply-adds by each count, and bytes."""
    name: str
    op: str                 # "conv" | "fc"
    direct_macs: int        # the plain direct / dense count
    least_macs: int         # the fewest any route of the datapath needs
    in_bytes: int
    out_bytes: int
    weight_bytes: int

    @property
    def flops(self) -> int:
        """2 x the least multiply-adds, for one image."""
        return 2 * self.least_macs

    def batch_flops(self, rows: int) -> int:
        return rows * self.flops

    def batch_bytes(self, rows: int) -> int:
        """Bytes for one launch of ``rows`` images: activations per image,
        weights once."""
        return rows * (self.in_bytes + self.out_bytes) + self.weight_bytes

    def least_seconds(self, rows: int, peak_flops: float,
                      peak_bytes_per_s: float) -> float:
        """The least time a chip with these peaks could take for a launch
        of ``rows`` images: the larger of the compute and memory bounds."""
        return max(self.batch_flops(rows) / peak_flops,
                   self.batch_bytes(rows) / peak_bytes_per_s)


def conv_out(h: int, kernel: int, stride: int, padding: str) -> int:
    if padding == "SAME":
        return -(-h // stride)
    return (h - kernel) // stride + 1


def pool_out(h: int, window: int, stride: int) -> int:
    """VALID max-pool extent."""
    return (h - window) // stride + 1


def conv_macs(h_out: int, w_out: int, c_in: int, c_out: int, kernel: int,
              stride: int) -> tuple:
    """(direct, least) multiply-adds of one conv for one image; ``c_in`` is
    the channels each output channel reads (per group)."""
    direct = h_out * w_out * c_in * c_out * kernel * kernel
    if kernel != 3 or stride != 1:
        return direct, direct
    n = WINOGRAD_M + kernel - 1
    tiles = -(-h_out // WINOGRAD_M) * -(-w_out // WINOGRAD_M)
    return direct, min(direct, tiles * n * n * c_in * c_out)


def layer_work(cfg: dict) -> list:
    """Per-image :class:`LayerWork` of every layer of a configuration."""
    size = _DTYPE_BYTES[cfg["dtype"]]
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


def image_flops(cfg: dict) -> int:
    """Least operations of one image's whole forward."""
    return sum(w.flops for w in layer_work(cfg))
