"""Public entry points for the conv kernel family.

Two Pallas datapaths share one fused-layer contract (bias, ReLU, groups,
in-VMEM LRN + max-pool epilogue):

* :func:`conv2d` — the Winograd-domain kernel (``winograd.py``) for
  stride-1 layers; ``pallas=False`` falls back to the differentiable
  pure-jnp Winograd path in ``repro.core.winograd``.
* :func:`conv2d_direct` — the strided direct kernel (``direct.py``) for
  any kernel size / stride / groups (AlexNet conv1's 11x11 stride 4);
  ``pallas=False`` falls back to the ``lax.conv_general_dilated`` oracle.

The depthwise-causal op carries a custom VJP (Pallas kernels have no
autodiff rule): dx is the same Winograd kernel run on the time-reversed
cotangent, so the backward pass also hits the MXU kernel; dw/db are cheap
shifted reductions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import winograd as wg
from . import direct as _d
from . import winograd as _k
from .ref import conv2d_ref


def _interp(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


# ---------------------------------------------------------------------------
# depthwise causal conv1d
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dw1d(x, w, b, interpret):
    return _k.conv1d_depthwise_causal(x, w, b, interpret=interpret)


def _dw1d_fwd(x, w, b, interpret):
    return _dw1d(x, w, b, interpret), (x, w)


def _dw1d_bwd(interpret, res, dy):
    x, w = res
    r = w.shape[0]
    # dx[s] = sum_k w[k] dy[s + r-1-k]  == reverse(conv(reverse(dy), w))
    dy_rev = dy[:, ::-1, :]
    dx = _k.conv1d_depthwise_causal(dy_rev, w, None,
                                    interpret=interpret)[:, ::-1, :]
    # dw[k] = sum_{b,t} dy[t] * x[t - r + 1 + k]
    xp = jnp.pad(x, ((0, 0), (r - 1, 0), (0, 0)))
    L = x.shape[1]
    dw = jnp.stack([jnp.einsum("blc,blc->c", dy.astype(jnp.float32),
                               xp[:, k:k + L, :].astype(jnp.float32))
                    for k in range(r)], axis=0).astype(w.dtype)
    db = dy.sum(axis=(0, 1)).astype(w.dtype)
    return dx.astype(x.dtype), dw, db


_dw1d.defvjp(_dw1d_fwd, _dw1d_bwd)


def conv1d_depthwise_causal(x, w, b=None, *, pallas: bool = True,
                            interpret: bool | None = None):
    if pallas:
        bb = jnp.zeros((w.shape[1],), w.dtype) if b is None else b
        return _dw1d(x, w, bb, _interp(interpret))
    return wg.conv1d_depthwise_causal(x, w, b)


# ---------------------------------------------------------------------------
# 2D conv (inference path; training uses the differentiable jnp route)
# ---------------------------------------------------------------------------
def conv2d(x, w, b=None, w_packed=None, *, m: int = 4, padding: str = "SAME",
           relu: bool = False, groups: int = 1, lrn=None, pool=None,
           c_block: int | None = None, pool_row_block: int | None = None,
           k_block: int = 128, batch_block: int = 8,
           weight_prefetch: bool = True, row_parallel: bool = False,
           checksum: bool = False, pallas: bool = True,
           interpret: bool | None = None, name: str | None = None):
    """Fused stride-1 Winograd conv layer: bias, ReLU, groups, LRN, pool.

    Both routes share one signature so they stay numerically
    interchangeable: ``pallas=True`` runs the stream-buffered Pallas kernel
    (in-kernel tiling + channel-block reduction + in-VMEM LRN/pool
    epilogue + filter-cache batch grid + double-buffered manual-DMA weight
    stream), ``pallas=False`` the differentiable pure-jnp Winograd path.
    ``lrn`` is an :class:`repro.nn.pooling.LrnParams` (or None); ``pool``
    is a (window, stride) pair for a VALID max-pool (or None).
    ``w_packed``/``weight_prefetch`` reach the Pallas weight pipeline only
    (the jnp route has no weight stream to stage).

    ``checksum=True`` arms the ABFT weight-stream verification and both
    routes return ``(y, verdict)`` — the jnp route has no DMA stream to
    corrupt, so its verdict is the constant 0 (the contract stays uniform
    for ``nn.conv.dispatch_conv``).  ``name`` names the Pallas kernel.
    """
    if pallas:
        return _k.conv2d_winograd(x, w, b, w_packed, m=m, padding=padding,
                                  relu=relu, groups=groups, lrn=lrn,
                                  pool=pool, c_block=c_block,
                                  pool_row_block=pool_row_block,
                                  k_block=k_block,
                                  batch_block=batch_block,
                                  weight_prefetch=weight_prefetch,
                                  row_parallel=row_parallel,
                                  checksum=checksum,
                                  interpret=_interp(interpret), name=name)
    y = wg.conv2d_winograd(x, w, b, m=m, padding=padding, relu=relu,
                           groups=groups, lrn=lrn, pool=pool)
    return (y, jnp.zeros((), jnp.int32)) if checksum else y


def conv2d_direct(x, w, b=None, w_packed=None, *, stride: int = 1,
                  padding="SAME", relu: bool = False, groups: int = 1,
                  lrn=None, pool=None, c_block: int | None = None,
                  pool_row_block: int | None = None, k_block: int = 128,
                  batch_block: int = 8,
                  weight_prefetch: bool = True, row_parallel: bool = False,
                  checksum: bool = False, pallas: bool = True,
                  interpret: bool | None = None, name: str | None = None,
                  residual=None):
    """Fused direct conv layer for any kernel/stride geometry.

    ``pallas=True`` runs the strided stream-buffered kernel (``direct.py``)
    — AlexNet's conv1/conv2 datapath on the ``pallas`` route;
    ``pallas=False`` is the ``lax.conv_general_dilated`` oracle with the
    same fused-layer signature (``ref.conv2d_ref``).  ``checksum=True``
    returns ``(y, verdict)`` on both routes (constant 0 off-Pallas).
    ``name`` names the Pallas kernel.  ``residual``, a shortcut of the
    output's shape, is added before the ReLU (no LRN or pool then).
    """
    if pallas:
        return _d.conv2d_direct(x, w, b, w_packed, residual, stride=stride,
                                padding=padding, relu=relu, groups=groups,
                                lrn=lrn, pool=pool, c_block=c_block,
                                pool_row_block=pool_row_block,
                                k_block=k_block,
                                batch_block=batch_block,
                                weight_prefetch=weight_prefetch,
                                row_parallel=row_parallel,
                                checksum=checksum,
                                interpret=_interp(interpret), name=name)
    if residual is not None:
        y = conv2d_ref(x, w, b, stride=stride, padding=padding,
                       groups=groups) + residual.astype(x.dtype)
        y = jnp.maximum(y, 0) if relu else y
    else:
        y = conv2d_ref(x, w, b, stride=stride, padding=padding,
                       groups=groups, relu=relu, lrn=lrn, pool=pool)
    return (y, jnp.zeros((), jnp.int32)) if checksum else y
