"""Pure-jnp oracles for the Winograd kernels: direct convolution."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def conv1d_depthwise_causal_ref(x, w, b=None):
    """Direct (shift-multiply) causal depthwise conv; x (B,L,C), w (r,C)."""
    r = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (r - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + x.shape[1], :] * w[i].astype(x.dtype)
            for i in range(r))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def conv2d_ref(x, w, b=None, *, stride: int = 1, padding="SAME",
               groups: int = 1, relu: bool = False, lrn=None, pool=None):
    """lax direct conv with the fused-layer signature.

    x (B,H,W,C), w (r,r,C//groups,K); ``padding`` "SAME", "VALID" or
    explicit ``(lo, hi)`` pads on each spatial side; optional bias (K,),
    fused ReLU, grouped convolution via ``feature_group_count``, and the
    layer epilogue — cross-channel LRN (``lrn``: LrnParams) then VALID
    max-pool (``pool``: (window, stride)) — the oracle for every route of
    ``repro.nn.conv.dispatch_conv``.
    """
    pads = padding if isinstance(padding, str) else (tuple(padding),) * 2
    y = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride), padding=pads,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)
    if b is not None:
        y = y + b.astype(y.dtype)
    if relu:
        y = jnp.maximum(y, 0.0)
    if lrn is not None or pool is not None:
        # function-level import: nn.pooling sits above this module in the
        # package graph (nn.conv imports this file at import time)
        from ...nn.pooling import apply_epilogue
        y = apply_epilogue(y, lrn, pool)
    return y.astype(x.dtype)
