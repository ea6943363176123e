"""What every net's plain reference shares: the seeded weights and images,
the precisions, and the reference run in blocks.

A net's own reference (``bench/nets/<net>/reference.py``) writes its
parameter shapes and its forward pass in straightforward jnp at float32,
and imports nothing of the program.  Its weights are the harness's own,
made on the device from the seed in one jitted call; the program is given
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

A net's forward computes each of its products through :func:`product`,
which is where the precision is applied.
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


def he_normal_params(shapes: dict, dtype: str, seed: int) -> dict:
    """He-normal weights and N(0, 0.1) biases for ``{layer: {"w": shape,
    "b": shape}}`` (fan-in: every axis of ``w`` but the last), made on the
    default device in one jitted call, in ``dtype``."""
    dtype = jnp.dtype(dtype)

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
# precisions
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


def product(op, a, b, precision: str):
    """``op(a, b, lax_precision)``, a bilinear product such as a conv or a
    dot, at ``precision``: a name in ``PRECISIONS`` or ``"bf16_3x"``."""
    if precision == "bf16_3x":
        return _three_pass(lambda x, y: op(x, y, PRECISIONS["highest"]), a, b)
    return op(a, b, PRECISIONS[precision])


# ---------------------------------------------------------------------------
# the reference over a pool of images
# ---------------------------------------------------------------------------
def logits(forward, cfg: dict, params: dict, images: np.ndarray,
           precision: str, block: int) -> np.ndarray:
    """A net's ``forward`` over ``images`` in blocks of ``block`` rows (the
    last block padded), on the host."""
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
