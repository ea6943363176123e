"""The plain reference of the net ``resnet``: a graph of convs, each
followed by inference batch norm, with residual adds, a padded max-pool,
a global average pool and an FC layer, as a configuration file's
``layers`` table lists them (ResNet-50 v1.5).

Straightforward jnp at float32; it imports nothing of the program.  Each
conv pads as the table writes it, ``[lo, hi]`` on every spatial side,
and batch norm is applied unfolded after it: (y - mean) / sqrt(var + eps)
* gamma + beta.  A conv with ``add`` then adds that tensor, before its
ReLU.  The seeded images, the precisions and the blocked ``logits`` are
the harness's (``bench/reference.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference as common


def shapes(cfg: dict) -> dict:
    """``{layer: (out_hw, channels)}`` of every tensor, ``image`` too."""
    out = {"image": (cfg["image_size"], cfg["in_channels"])}
    for layer in cfg["layers"]:
        h, c = out[layer["input"]]
        if layer["op"] in ("conv", "maxpool"):
            k = layer["kernel"] if layer["op"] == "conv" else layer["window"]
            lo, hi = layer["padding"]
            h = (h + lo + hi - k) // layer["stride"] + 1
            c = layer.get("out", c)
        elif layer["op"] == "avgpool":
            h = 1
        else:
            h, c = 1, layer["out"]
        out[layer["name"]] = (h, c)
    return out


# the batch whose statistics become the running statistics, and the
# precision they are computed at (its compile is a third of ``highest``'s)
CALIBRATION_IMAGES = 8
CALIBRATION_PRECISION = "high"


def make_params(cfg: dict, seed: int) -> dict:
    """From the seed, on the device in one jitted call: He-normal conv
    filters (HWIO, no bias) with their batch norm, ``{"w", "bn": {"gamma",
    "beta", "mean", "var"}}``, and the FC's He-normal ``w`` and N(0, 0.1)
    ``b``.  gamma is U(0.5, 1.5) and beta N(0, 0.1); mean and var are
    what training leaves them, the statistics of each conv's output, here
    over a seeded batch of N(0, 1) images that the pool does not hold.
    So every batch norm normalizes as a trained one does, activations stay
    of order one through the 16 residual adds, and each residual branch
    stays of the order of its shortcut.  Each kind of value is one draw,
    cut into the layers' pieces."""
    dtype = jnp.dtype(cfg["dtype"])
    hw = shapes(cfg)
    layers = [l for l in cfg["layers"] if l["op"] in ("conv", "fc")]
    w_shapes = [(l["kernel"],) * 2 + (hw[l["input"]][1], l["out"])
                if l["op"] == "conv" else (hw[l["input"]][1], l["out"])
                for l in layers]
    w_ends = np.cumsum([int(np.prod(s)) for s in w_shapes])
    c_ends = np.cumsum([l["out"] for l in layers])

    def init(key):
        key_w, key_g, key_b, key_x = jax.random.split(key, 4)
        w_all = jax.random.normal(key_w, (int(w_ends[-1]),))
        gamma = jax.random.uniform(key_g, (int(c_ends[-1]),), minval=0.5,
                                   maxval=1.5)
        beta = 0.1 * jax.random.normal(key_b, (int(c_ends[-1]),))
        out = {}
        for layer, shape, w_end, c_end in zip(layers, w_shapes, w_ends,
                                              c_ends):
            w = w_all[w_end - int(np.prod(shape)):w_end].reshape(shape)
            ch = slice(c_end - layer["out"], c_end)
            p = {"w": w * np.sqrt(2.0 / int(np.prod(shape[:-1])))}
            if layer["op"] == "conv":
                p["bn"] = {"gamma": gamma[ch], "beta": beta[ch]}
            else:
                p["b"] = beta[ch]
            out[layer["name"]] = p
        h, c = hw["image"]
        images = jax.random.normal(key_x, (CALIBRATION_IMAGES, h, h, c))
        forward(cfg, out, images, CALIBRATION_PRECISION, calibrate=True)
        return jax.tree.map(lambda a: a.astype(dtype), out)

    return jax.jit(init)(jax.random.fold_in(common.seed_key(seed), 1))


def _conv(x, w, layer, precision):
    pad = tuple(layer["padding"])

    def op(a, b, p):
        return jax.lax.conv_general_dilated(
            a, b, (layer["stride"],) * 2, (pad, pad),
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=p,
            preferred_element_type=jnp.float32)
    return common.product(op, x, w, precision)


def _dense(x, w, precision):
    def op(a, b, p):
        return jnp.dot(a, b, precision=p, preferred_element_type=jnp.float32)
    return common.product(op, x, w, precision)


def _batch_norm(y, bn, eps: float):
    f32 = jnp.float32
    return ((y - bn["mean"].astype(f32))
            / jnp.sqrt(bn["var"].astype(f32) + eps)
            * bn["gamma"].astype(f32) + bn["beta"].astype(f32))


def _maxpool(x, layer):
    k, s = layer["window"], layer["stride"]
    pad = tuple(layer["padding"])
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, k, k, 1),
                                 (1, s, s, 1), ((0, 0), pad, pad, (0, 0)))


def forward(cfg: dict, params: dict, x, precision: str = "highest",
            *, acts: dict | None = None, calibrate: bool = False):
    """Logits (B, classes) in float32 for images ``x`` (B, H, W, C).
    ``acts``, a dict, receives every tensor by its layer's name.  With
    ``calibrate`` each batch norm's ``mean`` and ``var`` are first set, in
    ``params``, to its conv output's statistics over ``x``."""
    acts = {} if acts is None else acts
    acts["image"] = x.astype(jnp.float32)
    eps = cfg["bn"]["eps"]
    for layer in cfg["layers"]:
        x = acts[layer["input"]]
        if layer["op"] == "conv":
            p = params[layer["name"]]
            x = _conv(x, p["w"], layer, precision)
            if calibrate:
                p["bn"]["mean"] = x.mean(axis=(0, 1, 2))
                p["bn"]["var"] = x.var(axis=(0, 1, 2))
            x = _batch_norm(x, p["bn"], eps)
            if layer["add"]:
                x = x + acts[layer["add"]]
        elif layer["op"] == "maxpool":
            x = _maxpool(x, layer)
        elif layer["op"] == "avgpool":
            x = x.mean(axis=(1, 2))
        else:
            p = params[layer["name"]]
            x = _dense(x, p["w"], precision) + p["b"].astype(jnp.float32)
        if layer.get("relu"):
            x = jnp.maximum(x, 0.0)
        acts[layer["name"]] = x
    return x
