"""The plain reference of the net ``chain``: conv layers with fused ReLU,
LRN and max-pool, then FC layers, as a configuration file's ``layers``
table lists them (AlexNet, VGG-16).

Straightforward jnp at float32; it imports nothing of the program.  The
seeded images, the precisions and the blocked ``logits`` are the
harness's, shared by every net (``bench/reference.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference as common


def param_shapes(cfg: dict) -> dict:
    """``{layer: {"w": shape, "b": shape}}``: conv weights HWIO (input
    channels per group), dense weights (in, out)."""
    shapes, h, c, flat = {}, cfg["image_size"], cfg["in_channels"], None
    for layer in cfg["layers"]:
        if layer["op"] == "conv":
            k, g = layer["kernel"], layer["groups"]
            shapes[layer["name"]] = {"w": (k, k, c // g, layer["out"]),
                                     "b": (layer["out"],)}
            h = _conv_hw(h, layer)
            c = layer["out"]
        else:
            d_in = flat if flat is not None else h * h * c
            shapes[layer["name"]] = {"w": (d_in, layer["out"]),
                                     "b": (layer["out"],)}
            flat = layer["out"]
    return shapes


def _conv_hw(h: int, layer: dict) -> int:
    k, s = layer["kernel"], layer["stride"]
    h = -(-h // s) if layer["padding"] == "SAME" else (h - k) // s + 1
    if layer["pool"]:
        w, ps = layer["pool"]
        h = (h - w) // ps + 1
    return h


def make_params(cfg: dict, seed: int) -> dict:
    """He-normal weights and N(0, 0.1) biases from the seed."""
    return common.he_normal_params(param_shapes(cfg), cfg["dtype"], seed)


def _conv(x, w, layer, precision):
    def op(a, b, p):
        return jax.lax.conv_general_dilated(
            a, b, (layer["stride"],) * 2, layer["padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=layer["groups"], precision=p,
            preferred_element_type=jnp.float32)
    return common.product(op, x, w, precision)


def _dense(x, w, precision):
    def op(a, b, p):
        return jnp.dot(a, b, precision=p, preferred_element_type=jnp.float32)
    return common.product(op, x, w, precision)


def _lrn(x, p):
    """y[c] = x[c] / (k + alpha/n * sum_{|d| <= n//2} x[c+d]^2)^beta,
    channels past either end counted as zero."""
    half = p["n"] // 2
    sq = jnp.pad(jnp.square(x), ((0, 0),) * 3 + ((half, half),))
    c = x.shape[-1]
    win = sum(sq[..., d:d + c] for d in range(p["n"]))
    return x / jnp.power(p["k"] + p["alpha"] / p["n"] * win, p["beta"])


def _maxpool(x, window, stride):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, window, window, 1),
                                 (1, stride, stride, 1), "VALID")


def forward(cfg: dict, params: dict, x, precision: str = "highest"):
    """Logits (B, classes) in float32 for images ``x`` (B, H, W, C)."""
    x = x.astype(jnp.float32)
    for layer in cfg["layers"]:
        p = params[layer["name"]]
        if layer["op"] == "conv":
            x = _conv(x, p["w"], layer, precision) + p["b"].astype(jnp.float32)
            if layer["relu"]:
                x = jnp.maximum(x, 0.0)
            if layer["lrn"]:
                x = _lrn(x, cfg["lrn"])
            if layer["pool"]:
                x = _maxpool(x, *layer["pool"])
        else:
            if x.ndim > 2:
                x = x.reshape(x.shape[0], -1)
            x = _dense(x, p["w"], precision) + p["b"].astype(jnp.float32)
            if layer["relu"]:
                x = jnp.maximum(x, 0.0)
    return x
