"""Shared in-kernel layer-epilogue machinery for the fused conv kernels.

Both Pallas conv kernels — the Winograd-domain kernel (``winograd.py``) and
the strided direct kernel (``direct.py``) — end the same way (paper §3.5):
per-K-block bias+ReLU results are deposited into a full-channel VMEM
scratch, and the very last (k, c) grid step runs the cross-channel LRN and
VALID max-pool entirely in VMEM before writing only the pooled, normalized
feature map to HBM.  This module is that shared tail — the epilogue math
exists exactly once — plus the host-side channel/batch block helpers both
``pallas_call`` setups use.  The direct kernel can also add a residual
(a bottleneck's shortcut, a second VMEM operand tiled like its K block)
between the bias and the ReLU, in the layers that have no LRN or pool.

Everything here runs *inside* a kernel (on VMEM-resident arrays) except the
``*_blocks`` and ``vmem_limit`` helpers, which are host-side setup.

The full-channel scratch is *chunked* by output-channel block,
``(Bb, n_kblocks, rows, cols, Kb)``: Mosaic's strided VMEM loads and
stores (the max-pool windows here, the Winograd tile gather and scatter)
need a buffer of at most 128 lanes, and every K block is at most that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.winograd import auto_c_block, vmem_bytes

# every GEMM of the conv datapath, in the kernels and in the filter
# transform ahead of them: float32 operands contracted at full float32.  A
# dot without it runs at the TPU default, which rounds operands to bfloat16
F32_DOT = jax.lax.Precision.HIGHEST

# scoped-VMEM request bounds: the v5e default scoped limit is 16 MiB of
# its 128 MiB, and Mosaic keeps in-kernel values on top of the buffers
_VMEM_FLOOR = 32 * 2 ** 20
_VMEM_CEILING = 110 * 2 ** 20
# the most a launch's buffers may take and still leave the floor for
# Mosaic's values under the ceiling
VMEM_NEED_MAX = _VMEM_CEILING - _VMEM_FLOOR


# ---------------------------------------------------------------------------
# in-kernel epilogue stages
# ---------------------------------------------------------------------------
def lrn_banded(yf, lrn):
    """Cross-channel LRN on a VMEM-resident (rows, cols, K) f32 slab.

    The squared-sum over the +/- n//2 channel window is phrased as one
    (rows*cols, K) @ (K, K) banded matmul — MXU-shaped, like the conv GEMMs
    themselves — instead of a K-step reduce loop.
    """
    Kf = yf.shape[-1]
    half = lrn.n // 2
    ci = jax.lax.broadcasted_iota(jnp.int32, (Kf, Kf), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (Kf, Kf), 1)
    band = (jnp.abs(ci - cj) <= half).astype(jnp.float32)
    win = jax.lax.dot_general(
        (yf * yf).reshape(-1, Kf), band, (((1,), (0,)), ((), ())),
        precision=F32_DOT,
        preferred_element_type=jnp.float32).reshape(yf.shape)
    return yf / jnp.power(lrn.k + lrn.alpha / lrn.n * win, lrn.beta)


def maxpool_strided(y_ref, idx, pool, pr: int, pw: int):
    """VALID max-pool of the (rows, cols, Kb) plane ``y_ref[idx]`` via
    window**2 strided VMEM loads."""
    pwin, ps = pool
    yp = None
    for di in range(pwin):
        for dj in range(pwin):
            sl = y_ref[(*idx, pl.ds(di, pr, stride=ps),
                        pl.ds(dj, pw, stride=ps), slice(None))]
            yp = sl if yp is None else jnp.maximum(yp, sl)
    return yp


def fused_epilogue(y_ref, out_ref, bi, lrn, pool):
    """LRN (or None) then max-pool (or None) of image slot ``bi``'s
    chunked full-channel scratch ``y_ref[bi]`` (n_kblocks, rows, cols, Kb),
    written to ``out_ref[bi]`` (pr, pw, n_kblocks * Kb).

    LRN windows cross K-block seams, so it runs on the lane-concatenated
    channels and writes the normalized chunks back; the pool then reads
    each chunk with strided loads.  Without a pool the first ``pr`` rows
    are written (trailing rows belong to the next step or are padding).
    """
    nk, Kb = y_ref.shape[1], y_ref.shape[-1]
    pr, pw = out_ref.shape[1], out_ref.shape[2]
    if lrn is not None:
        yf = jnp.concatenate([y_ref[bi, j] for j in range(nk)], axis=-1)
        yf = lrn_banded(yf, lrn)
        for j in range(nk):
            y_ref[bi, j] = yf[..., j * Kb:(j + 1) * Kb]
    for j in range(nk):
        if pool is not None:
            blk = maxpool_strided(y_ref, (bi, j), pool, pr, pw)
        else:
            blk = y_ref[bi, j, :pr, :pw]
        out_ref[bi, :, :, j * Kb:(j + 1) * Kb] = blk.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# host-side block helpers shared by both pallas_call setups
# ---------------------------------------------------------------------------
def channel_blocks(C: int, c_block: int | None, hp: int, wp: int,
                   batch: int = 1, *, groups: int = 1,
                   max_block: int | None = None,
                   dtype_bytes: int = 4) -> int:
    """Channel block size: explicit, or auto-sized (lane-legal, padded,
    double-buffered) so the whole resident (batch, hp, wp, Cb) input block
    fits the VMEM slab budget — see ``auto_c_block``."""
    if c_block is None:
        return auto_c_block(hp, wp, C, batch=batch, groups=groups,
                            max_block=max_block, dtype_bytes=dtype_bytes)
    return min(c_block, C)


def vmem_need(pipelined, scratch, dtype_bytes: int = 4) -> int:
    """VMEM bytes one kernel launch's buffers take: the grid pipeline
    double-buffers every ``pipelined`` block shape, ``scratch`` shapes are
    allocated once, all padded to the (8, 128) tile."""
    return (2 * sum(vmem_bytes(s, dtype_bytes) for s in pipelined)
            + sum(vmem_bytes(s, dtype_bytes) for s in scratch))


def vmem_limit(pipelined, scratch, dtype_bytes: int = 4) -> int:
    """Scoped-VMEM bytes to request for one kernel launch: its buffers
    (:func:`vmem_need`) and the same again (at least the floor) for
    Mosaic's in-kernel values."""
    need = vmem_need(pipelined, scratch, dtype_bytes)
    return int(min(max(2 * need, need + _VMEM_FLOOR), _VMEM_CEILING))


def k_blocks(K: int, k_block: int) -> int:
    """Output-channel block.  Blocks must tile K *exactly*: zero-pad channels
    inside an LRN window would shadow the real cross-seam neighbours, so a
    non-dividing ``k_block`` widens to K."""
    Kb = min(k_block, K)
    return K if K % Kb else Kb


def batch_blocks(B: int, batch_block: int) -> tuple[int, int]:
    """(Bb, Bp): filter-cache depth and the zero-padded batch extent.

    ``Bb`` images ride in the innermost grid dimension with the weight-block
    index held constant, so each weight tile streams HBM->VMEM once per
    ``Bb`` images — the paper's §3.5 filter cache (weights reused across the
    batch) rather than once per image.
    """
    Bb = max(1, min(batch_block, B))
    return Bb, -(-B // Bb) * Bb


def grouped_channel_pad(x, g: int, Cb: int):
    """(B,H,W,g*C) -> (B,H,W,g*Cp) with each group's channels zero-padded to
    a ``Cb`` multiple (group-major layout, so the kernel's channel-block
    index ``(k // nkb) * ncb + c`` lands on the right group)."""
    B, H, W, Ct = x.shape
    C = Ct // g
    padc = (-C) % Cb
    if not padc:
        return x, C
    x5 = x.reshape(B, H, W, g, C)
    x5 = jnp.pad(x5, ((0, 0), (0, 0), (0, 0), (0, 0), (0, padc)))
    return x5.reshape(B, H, W, g * (C + padc)), C
