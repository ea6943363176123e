"""The plain reference: a configuration's forward in straightforward jnp.

It reads the configuration file's layer table and imports nothing of the
program under test.  Its weights are the harness's own (``make_params``,
made on the device from the seed in one jitted call); the program is given
the same arrays.

* ``forward(cfg, params, x, "highest")`` is the reference: float32 at the
  highest matmul precision, as the configuration states.
* ``forward(cfg, params, x, "bf16_3x")`` is the control: the same forward
  with every conv and dense product in three bfloat16 passes (each operand
  split into a bfloat16 head and a bfloat16 tail; the tail x tail product
  dropped), the precision one step below the configuration's.  It is
  written out, not left to ``Precision.HIGH``, so that it computes the same
  on every backend, the CPU of the tests included.  The split rounds with
  ``reduce_precision``, which XLA keeps: a float32 -> bfloat16 -> float32
  round trip may be dropped on the TPU as excess precision, which made an
  earlier form of this control a one-pass bfloat16 product there.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# lax precisions by name; "bf16_3x" is the written-out three-pass control
PRECISIONS = {"highest": jax.lax.Precision.HIGHEST,
              "high": jax.lax.Precision.HIGH}


# ---------------------------------------------------------------------------
# weights and images from the seed
# ---------------------------------------------------------------------------
def seed_key(seed: int):
    """A PRNG key for any whole number up to 2**63: the low 31 bits make
    the key, the rest is folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


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
    """He-normal weights and N(0, 0.1) biases, made on the default device
    in one jitted call, in the configuration's dtype."""
    shapes = param_shapes(cfg)
    dtype = jnp.dtype(cfg["dtype"])

    def init(key):
        out = {}
        for i, (name, s) in enumerate(sorted(shapes.items())):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            fan_in = int(np.prod(s["w"][:-1]))
            out[name] = {
                "w": (jax.random.normal(kw, s["w"], jnp.float32)
                      * np.sqrt(2.0 / fan_in)).astype(dtype),
                "b": (0.1 * jax.random.normal(kb, s["b"], jnp.float32)
                      ).astype(dtype)}
        return out

    return jax.jit(init)(jax.random.fold_in(seed_key(seed), 1))


def make_images(cfg: dict, seed: int, n: int) -> np.ndarray:
    """``n`` N(0, 1) images (n, H, W, C), made on the device, on the host."""
    s = (n, cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    dtype = jnp.dtype(cfg["dtype"])
    x = jax.jit(lambda k: jax.random.normal(k, s, jnp.float32).astype(dtype))(
        jax.random.fold_in(seed_key(seed), 2))
    return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# the forward
# ---------------------------------------------------------------------------
def _bf16(x):
    """``x`` rounded to bfloat16's 8 mantissa bits, kept in float32."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _split(x):
    hi = _bf16(x)
    return hi, _bf16(x - hi)


def _three_pass(op, a, b):
    """``op(a, b)`` in three bfloat16 passes accumulated in float32: each
    pass multiplies bfloat16 values, exactly, at the highest precision."""
    ah, al = _split(a.astype(jnp.float32))
    bh, bl = _split(b.astype(jnp.float32))
    return op(ah, bh) + op(ah, bl) + op(al, bh)


def _conv(x, w, layer, precision):
    def op(a, b, p):
        return jax.lax.conv_general_dilated(
            a, b, (layer["stride"],) * 2, layer["padding"],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=layer["groups"], precision=p,
            preferred_element_type=jnp.float32)
    return _apply(op, x, w, precision)


def _dense(x, w, precision):
    def op(a, b, p):
        return jnp.dot(a, b, precision=p, preferred_element_type=jnp.float32)
    return _apply(op, x, w, precision)


def _apply(op, a, b, precision):
    if precision == "bf16_3x":
        return _three_pass(lambda x, y: op(x, y, PRECISIONS["highest"]), a, b)
    return op(a, b, PRECISIONS[precision])


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
    assert precision in PRECISIONS or precision == "bf16_3x", precision
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


def logits(cfg: dict, params: dict, images: np.ndarray, precision: str,
           block: int) -> np.ndarray:
    """``forward`` over ``images`` in blocks of ``block`` rows (the last
    block padded), on the host."""
    fn = jax.jit(lambda p, x: forward(cfg, p, x, precision))
    out = []
    for i in range(0, len(images), block):
        part = images[i:i + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.zeros((pad,) + part.shape[1:],
                                                  part.dtype)])
        out.append(np.asarray(fn(params, part))[:block - pad])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)
