"""Pallas TPU kernels for Winograd-domain convolution (paper §3.3 + §3.5).

Hardware adaptation (docs/DESIGN.md): the paper's PEs do scalar Winograd-
domain dot products on DSP blocks; on TPU the Winograd-domain multiply must
feed the MXU, so we use the Lavin formulation — each of the n^2 transform
positions becomes an independent (tiles x C) @ (C x K) GEMM.

Stream-buffered dataflow (paper §3.5): the kernels read *raw* feature-map
slabs from HBM — no host-side tile gather, so the ~(n/m)^2-inflated
overlapping-tile tensor never materializes in HBM.  The Pallas grid
pipeline's double-buffered HBM->VMEM DMA plays the role of the DLA's stream
buffer; overlapping n x n tiles are built *in VMEM* from strided slices of
the slab.  A `c_block` grid dimension streams channel blocks with in-kernel
accumulation into a VMEM scratch (the PE "daisy-chained" partial sums), so
large-C layers never need all of C resident at once.  Bias + ReLU fuse into
the kernel epilogue (the DLA's post-PE activation stage) behind a flag.

Weight path (paper §3.5 filter prefetch — shared machinery in ``dma.py``):
the transformed filters arrive *tile-packed* in an ANY/HBM-space ref and
move by explicit ``pltpu.make_async_copy`` into a 2-slot VMEM scratch.  At
each (k, c) weight-tile transition the next tile's copy is issued before
this step's GEMMs and the only wait is the slot swap, so the filter stream
is double-buffered under MXU compute — the DLA's filter-cache data mover.
The grid still iterates ``batch_block`` images innermost with the tile
held constant (the §3.5 filter cache: one fetch per ``batch_block``
images), and ``plan``/``pack_weights`` expose the packing — including the
G w G^T filter transform — as a pure function of shapes so a model can
stage layer N+1's slab while layer N computes
(``nn/conv.py::pack_conv_weights``).

Grouped convolution folds groups into the K grid dimension (weight tile
``k * ncb + c`` on the group-major channel layout), so conv2/4/5 of
AlexNet run as one kernel launch with no host loop or concatenate — and
the fused epilogue sees the full concatenated channel dim (LRN windows
cross group seams).

The in-kernel LRN + max-pool epilogue lives in ``epilogue.py``, shared with
the strided direct kernel (``direct.py``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.winograd import LANES, auto_pool_rows, winograd_transform
from ..compat import PARALLEL, tpu_compiler_params
from . import dma
from .epilogue import F32_DOT, batch_blocks, channel_blocks, fused_epilogue, \
    grouped_channel_pad, k_blocks, vmem_limit


# ---------------------------------------------------------------------------
# 1D depthwise causal (Mamba conv, k=4 -> F(3,4))
# ---------------------------------------------------------------------------
def _dw1d_kernel(x_ref, w_ref, b_ref, bt_ref, g_ref, at_ref, out_ref):
    mm, n = at_ref.shape
    Tb = out_ref.shape[1] // mm
    jb = pl.program_id(1)
    # raw slab -> overlapping tiles in VMEM (stride-m strided slices)
    seg = x_ref[0, pl.ds(jb * Tb * mm, Tb * mm + n - mm)]  # (Tb*m + r - 1, Cb)
    Cb = seg.shape[-1]
    tiles = jnp.stack(
        [jax.lax.slice(seg, (di, 0), (di + (Tb - 1) * mm + 1, Cb), (mm, 1))
         for di in range(n)], axis=0).astype(jnp.float32)   # (n, Tb, Cb)
    w = w_ref[...].astype(jnp.float32)              # (r, Cb)
    BT = bt_ref[...]                                # (n, n)
    G = g_ref[...]                                  # (n, r)
    AT = at_ref[...]                                # (m, n)
    u = jnp.einsum("tn,njc->tjc", BT, tiles)        # input transform
    v = jnp.einsum("tr,rc->tc", G, w)               # filter transform
    y = jnp.einsum("mt,tjc->jmc", AT, u * v[:, None])  # mult + inverse
    y = y.reshape(Tb * mm, Cb) + b_ref[0]
    out_ref[0] = y.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("m", "tile_block", "c_block",
                                             "interpret"))
def conv1d_depthwise_causal(x, w, b=None, *, m: int | None = None,
                            tile_block: int = 128, c_block: int = 128,
                            interpret: bool = True):
    """x (B,L,C); w (r,C); left-padded causal depthwise conv via F(m,r).

    The kernel reads the raw padded sequence; overlapping n-tiles are built
    in VMEM (no host-side ``jnp.take`` tile materialization).  Stream-buffer
    residency: one (Lp, c_block) sequence slab stays in VMEM — ``c_block``
    bounds the footprint (Lp * c_block * 4 B must fit; e.g. L=8k, Cb=128
    -> ~4 MB).  Shrink ``c_block`` for very long sequences.
    """
    r = w.shape[0]
    m = m or {3: 4, 4: 3}.get(r, 2)
    t = winograd_transform(m, r)
    B, L, C = x.shape
    nt = -(-L // t.m)
    Tb = min(tile_block, nt)
    ntp = -(-nt // Tb) * Tb
    # left halo r-1; right pad so every tile block has a full slab
    xp = jnp.pad(x, ((0, 0), (r - 1, ntp * t.m - L + (t.n - t.m) - (r - 1)),
                     (0, 0)))
    Cb = min(c_block, C)
    padc = (-C) % Cb
    if padc:
        xp = jnp.pad(xp, ((0, 0), (0, 0), (0, padc)))
        w = jnp.pad(w, ((0, 0), (0, padc)))
    Cp = C + padc
    bias = jnp.zeros((Cp,), x.dtype) if b is None else (
        jnp.pad(b, (0, padc)) if padc else b)
    Lp = xp.shape[1]

    out = pl.pallas_call(
        _dw1d_kernel,
        grid=(B, ntp // Tb, Cp // Cb),
        in_specs=[
            pl.BlockSpec((1, Lp, Cb), lambda bb, j, c: (bb, 0, c)),
            pl.BlockSpec((r, Cb), lambda bb, j, c: (0, c)),
            pl.BlockSpec((1, Cb), lambda bb, j, c: (0, c)),
            pl.BlockSpec((t.n, t.n), lambda bb, j, c: (0, 0)),
            pl.BlockSpec((t.n, r), lambda bb, j, c: (0, 0)),
            pl.BlockSpec((t.m, t.n), lambda bb, j, c: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Tb * t.m, Cb), lambda bb, j, c: (bb, j, c)),
        out_shape=jax.ShapeDtypeStruct((B, ntp * t.m, Cp), x.dtype),
        compiler_params=tpu_compiler_params(PARALLEL, PARALLEL, PARALLEL),
        interpret=interpret,
    )(xp, w, bias.reshape(1, Cp), jnp.asarray(t.BT, jnp.float32),
      jnp.asarray(t.G, jnp.float32), jnp.asarray(t.AT, jnp.float32))

    return out[:, :L, :C]


# ---------------------------------------------------------------------------
# 2D conv (AlexNet 3x3 -> F(4,3) x F(4,3))
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class WinogradPlan:
    """Host-side launch plan for one 2D Winograd kernel call.

    Pure function of shapes + static params (``plan``), so the weight
    packing — including the G w G^T filter transform — can run ahead of
    the input tensor (the cross-layer staging hook).  ``fused`` selects
    the layer-fused grid (in-VMEM LRN/pool epilogue, exact K tiling) vs
    the plain conv grid (bias+ReLU only, K padded up to the block).
    """
    fused: bool
    m: int
    r: int
    g: int
    C: int                  # channels per group
    K: int                  # out channels per group
    out_h: int
    out_w: int
    ph_pad: int             # SAME halo pad (both sides)
    tw: int                 # width tiles
    Rt: int                 # tile rows per row step
    row_step: int           # tile rows advanced per row step
    npr: int                # row steps
    rows_out: int           # output rows written per row step
    w_out: int              # output cols written per row step
    thp: int                # total tile rows the slab must cover
    Hp: int
    Wp: int
    Bb: int
    Bp: int
    Cb: int
    Cp: int
    ncb: int
    Kb: int
    Kp: int                 # K per group incl. pad (== K when fused)
    nkb: int
    ph_out: int             # pooled rows (== out_h when no pool)
    pw_out: int
    checksum: bool = False  # ABFT checksum row on every weight tile

    @property
    def n(self) -> int:
        return self.m + self.r - 1

    @property
    def Kfull(self) -> int:
        return self.g * self.K

    @property
    def weights(self) -> dma.WeightPlan:
        return dma.WeightPlan(g=self.g, nkb=self.nkb, ncb=self.ncb,
                              Cb=self.Cb, Kb=self.Kb,
                              spatial=(self.n, self.n),
                              checksum=self.checksum)

    @property
    def vmem_limit_bytes(self) -> int:
        tile = (1, *self.weights.tile_shape)
        nk = self.g * self.nkb
        pipelined = [(self.Bb, self.Hp, self.Wp, self.Cb), (nk, self.Kb)]
        scratch = [(self.Bb, self.n * self.n, self.Rt * self.tw, self.Kb)]
        if self.fused:
            pipelined.append((self.Bb, self.rows_out, self.w_out,
                              self.Kfull))
            scratch.append((self.Bb, nk, self.Rt * self.m, self.tw * self.m,
                            self.Kb))
        else:
            pipelined.append((self.Bb, self.Rt * self.m, self.tw * self.m,
                              self.Kb))
        if self.weights.n_tiles == 1:
            pipelined.append(tile)
        else:
            scratch.append((2, *tile[1:]))
        return vmem_limit(pipelined, scratch)


def plan(x_shape, w_shape, *, m: int = 4, padding: str = "SAME",
         groups: int = 1, lrn=None, pool=None, row_block: int = 8,
         pool_row_block: int | None = None, c_block: int | None = None,
         k_block: int = 128, batch_block: int = 8,
         checksum: bool = False) -> WinogradPlan:
    """Derive the full launch plan from shapes + static params."""
    r = w_shape[0]
    t = winograd_transform(m, r)
    mm = t.m
    B, H, W, Ct = x_shape
    g = groups
    Kt = w_shape[-1]
    assert Ct % g == 0 and Kt % g == 0 and w_shape[2] == Ct // g, (
        "grouped conv shape mismatch")
    C, K = Ct // g, Kt // g
    if padding == "SAME":
        ph_pad = r // 2
        out_h, out_w = H, W
    else:
        ph_pad = 0
        out_h, out_w = H - r + 1, W - r + 1
    tw = -(-out_w // mm)
    Bb, Bp = batch_blocks(B, batch_block)
    fused = lrn is not None or pool is not None

    ph_out, pw_out = out_h, out_w
    if fused and pool is not None:
        pwin, ps = pool
        ph_out = (out_h - pwin) // ps + 1
        pw_out = (out_w - pwin) // ps + 1
        assert ph_out >= 1 and pw_out >= 1, (
            f"pool {pool} larger than conv output {out_h}x{out_w}")
        # alignment: each step's first conv row ps*Pb*i must be tile-aligned
        q = mm // math.gcd(ps, mm)
        if pool_row_block is None:
            # own the whole pooled extent when the epilogue scratch fits —
            # one row step, so grouped layers never re-fetch their slab
            Pb = auto_pool_rows(ph_out, pwin, ps, align=q, row_align=mm,
                                cols=tw * mm, kfull=g * K, batch=Bb)
        else:
            Pb = q * (-(-min(pool_row_block, ph_out) // q))
        row_step = ps * Pb // mm
        Rt = -(-(ps * (Pb - 1) + pwin) // mm)
        npr = -(-ph_out // Pb)
        rows_out, w_out = Pb, pw_out
        thp = (npr - 1) * row_step + Rt         # last step's read must fit
    else:
        th = -(-out_h // mm)
        Rt = row_step = min(row_block, th)
        npr = -(-th // Rt)
        rows_out, w_out = Rt * mm, tw * mm
        thp = (npr - 1) * row_step + Rt if fused else npr * Rt
    Hp = thp * mm + r - 1
    Wp = tw * mm + r - 1

    # the tile planes are stride-m VMEM loads, which take at most 128 lanes
    Cb = channel_blocks(C, c_block, Hp, Wp, Bb, groups=g, max_block=LANES)
    Cp = C + (-C) % Cb
    if fused:
        # no K padding: zero pad channels inside an LRN window would shadow
        # the real cross-seam neighbours, so blocks must tile K exactly
        Kb = k_blocks(K, k_block)
        Kp = K
    else:
        Kb = min(k_block, K)
        Kp = K + (-K) % Kb
    return WinogradPlan(fused=fused, m=m, r=r, g=g, C=C, K=K, out_h=out_h,
                        out_w=out_w, ph_pad=ph_pad, tw=tw, Rt=Rt,
                        row_step=row_step, npr=npr, rows_out=rows_out,
                        w_out=w_out, thp=thp, Hp=Hp, Wp=Wp, Bb=Bb, Bp=Bp,
                        Cb=Cb, Cp=Cp, ncb=Cp // Cb, Kb=Kb, Kp=Kp,
                        nkb=Kp // Kb, ph_out=ph_out, pw_out=pw_out,
                        checksum=checksum)


def pack_weights(w, p: WinogradPlan):
    """(r, r, C, g*K) raw filters -> (n_tiles, n, n, Cb, Kb) transformed
    DMA tiles: per-group G w G^T (host-side, tiny), channel/K pad, and the
    tile layout of ``dma.pack_weight_tiles``."""
    r, g, C, K = p.r, p.g, p.C, p.K
    t = winograd_transform(p.m, r)
    wg = jnp.moveaxis(w.reshape(r, r, C, g, K), 3, 0)       # (g, r, r, C, K)
    Gj = jnp.asarray(t.G, jnp.float32)
    wt = jnp.einsum("in,gnmck,jm->gijck", Gj, wg.astype(jnp.float32), Gj,
                    precision=F32_DOT)
    if p.Cp > C or p.Kp > K:
        wt = jnp.pad(wt, ((0, 0), (0, 0), (0, 0), (0, p.Cp - C),
                          (0, p.Kp - K)))
    return dma.pack_weight_tiles(wt, p.weights)


def _coeffs(mat):
    """Transform matrix rows as float32-rounded Python coefficients.  The
    least-squares construction leaves the transform's exact zeros at
    rounding-noise level; those are dropped, so the unrolled transforms
    below skip them."""
    mat = np.asarray(mat, np.float64)
    tol = 1e-9 * np.abs(mat).max()
    return tuple(tuple(float(np.float32(v)) if abs(v) > tol else 0.0
                       for v in row) for row in mat)


def _lincomb(coefs, terms):
    """sum_k coefs[k] * terms[k], unrolled over the non-zero constants —
    the Winograd transforms as VPU multiply-adds on whole VMEM planes."""
    acc = None
    for c, t in zip(coefs, terms):
        if c == 0.0:
            continue
        term = t if c == 1.0 else (-t if c == -1.0 else t * c)
        acc = term if acc is None else acc + term
    return acc


def _winograd_gemms(x_ref, acc_ref, v, bi, row0, *, BT, mm: int, nr: int,
                    nw: int):
    """Input transform + the n^2 Winograd-domain GEMMs of one grid step.

    Plane (di, dj) — element (di, dj) of every n x n tile of the ``nr`` x
    ``nw`` tile block starting at slab row ``row0`` — is one stride-m VMEM
    load of the raw slab (the overlapping tiles never exist anywhere).
    B^T d B runs separably over those planes, then each of the n^2
    positions is one (nr*nw, Cb) @ (Cb, Kb) MXU GEMM accumulated into the
    channel-block scratch (the PE partial sums)."""
    n = len(BT)
    Cb = x_ref.shape[-1]
    d = [[x_ref[bi, pl.ds(row0 + di, nr, stride=mm),
                pl.ds(dj, nw, stride=mm), :].astype(jnp.float32)
          for dj in range(n)] for di in range(n)]
    t = [[_lincomb(BT[i], [d[k][dj] for k in range(n)]) for dj in range(n)]
         for i in range(n)]
    for i in range(n):
        for j in range(n):
            u = _lincomb(BT[j], t[i]).reshape(nr * nw, Cb)
            acc_ref[bi, i * n + j] += jnp.dot(
                u, v[i, j], precision=F32_DOT,
                preferred_element_type=jnp.float32)


def _winograd_outputs(acc_ref, bi, AT):
    """Output transform A^T M A of the accumulated Winograd-domain block:
    yields ``(p, q, y)`` with ``y`` (nr*nw, Kb) the conv outputs at
    position (p, q) of every m x m output tile."""
    mm, n = len(AT), len(AT[0])
    acc = [acc_ref[bi, idx] for idx in range(n * n)]
    for p in range(mm):
        s = [_lincomb(AT[p], [acc[i * n + j] for i in range(n)])
             for j in range(n)]
        for q in range(mm):
            yield p, q, _lincomb(AT[q], s)


def _conv2d_kernel(x_ref, w_tiles, b_ref, out_ref, *refs, BT, AT,
                   relu: bool, checksum: bool, prefetch: bool, single: bool,
                   row_parallel: bool):
    if checksum:
        sdc_ref, acc_ref, wbuf, sem = refs
    else:
        acc_ref, wbuf, sem = refs
    mm = len(AT)
    Rb, tw = out_ref.shape[1] // mm, out_ref.shape[2] // mm
    Kb = out_ref.shape[-1]
    ib = pl.program_id(1)
    k = pl.program_id(2)
    c = pl.program_id(3)
    nc = pl.num_programs(3)
    bi = pl.program_id(4)                           # filter-cache image slot
    v = dma.fetch_weight_tile(w_tiles, wbuf, sem, prefetch=prefetch,
                              single=single, row_parallel=row_parallel)
    if checksum:
        # ABFT: verify the resident tile's checksum row, then strip it —
        # the GEMMs below consume exactly the same Cb rows as an unarmed
        # launch, so clean armed output is bit-identical
        dma.verify_tile_checksum(sdc_ref, v)
        v = v[..., :-1, :]
    v = v.astype(jnp.float32)

    @pl.when(c == 0)
    def _init():
        acc_ref[bi] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

    # raw slab rows for this tile-row block (halo overlap r-1 stays in VMEM)
    _winograd_gemms(x_ref, acc_ref, v, bi, ib * Rb * mm, BT=BT, mm=mm,
                    nr=Rb, nw=tw)

    @pl.when(c == nc - 1)
    def _epilogue():
        bias = b_ref[pl.ds(k, 1), :].astype(jnp.float32)
        for p, q, y in _winograd_outputs(acc_ref, bi, AT):
            y = y + bias
            if relu:
                y = jnp.maximum(y, 0.0)
            # output (p, q) of every tile: a stride-m store into the block
            out_ref[bi, pl.ds(p, Rb, stride=mm), pl.ds(q, tw, stride=mm),
                    :] = y.reshape(Rb, tw, Kb).astype(out_ref.dtype)


def _conv2d_fused_kernel(x_ref, w_tiles, b_ref, out_ref, *refs, BT, AT,
                         relu: bool, checksum: bool, lrn, pool,
                         row_step: int, prefetch: bool, single: bool,
                         row_parallel: bool):
    """Layer-fused variant: conv + bias + ReLU + LRN + max-pool in VMEM.

    The k grid dimension spans *all* g*K output channels (groups included);
    each (k, c=last) step deposits its channel block into the full-channel
    ``y_ref`` scratch, and the very last (k, c) step runs the cross-channel
    LRN + spatial max-pool epilogue (``epilogue.fused_epilogue``) and writes
    only the pooled, normalized slab to HBM — the conv-resolution feature
    map never leaves VMEM (§3.5).
    """
    if checksum:
        sdc_ref, acc_ref, y_ref, wbuf, sem = refs
    else:
        acc_ref, y_ref, wbuf, sem = refs
    mm = len(AT)
    _, _, rows, cols, Kb = y_ref.shape
    Rt, tw = rows // mm, cols // mm
    ib = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    c = pl.program_id(3)
    nc = pl.num_programs(3)
    bi = pl.program_id(4)                           # filter-cache image slot
    v = dma.fetch_weight_tile(w_tiles, wbuf, sem, prefetch=prefetch,
                              single=single, row_parallel=row_parallel)
    if checksum:
        dma.verify_tile_checksum(sdc_ref, v)
        v = v[..., :-1, :]
    v = v.astype(jnp.float32)

    @pl.when(c == 0)
    def _init():
        acc_ref[bi] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

    # raw slab rows for this output-owning block; successive blocks overlap
    # by Rt - row_step tile rows (the output-side pool halo, kept in VMEM)
    _winograd_gemms(x_ref, acc_ref, v, bi, ib * row_step * mm, BT=BT, mm=mm,
                    nr=Rt, nw=tw)

    @pl.when(c == nc - 1)
    def _store_kblock():
        bias = b_ref[pl.ds(k, 1), :].astype(jnp.float32)
        for p, q, y in _winograd_outputs(acc_ref, bi, AT):
            y = y + bias
            if relu:
                y = jnp.maximum(y, 0.0)
            # channel block k of the full-channel scratch (group-major)
            y_ref[bi, k, pl.ds(p, Rt, stride=mm), pl.ds(q, tw, stride=mm),
                  :] = y.reshape(Rt, tw, Kb)

    @pl.when((c == nc - 1) & (k == nk - 1))
    def _epilogue():
        fused_epilogue(y_ref, out_ref, bi, lrn, pool)


def _conv2d_fused_call(x, w, b, w_packed, *, t, p: WinogradPlan, relu,
                       lrn, pool, weight_prefetch, row_parallel, interpret,
                       name):
    """pallas_call setup for the layer-fused kernel (lrn and/or pool set).

    Grid (B/Bb, pooled-row blocks, g*K blocks, C blocks, Bb): groups move
    into the k dim so the epilogue sees the full concatenated channel dim —
    LRN windows legitimately cross group seams, as in Krizhevsky conv2 —
    and ``Bb = batch_block`` images iterate innermost so weight tiles stay
    VMEM-resident across images (the filter cache).  Each row step *owns a
    pooled output region*: it computes the Rt = ceil((ps*(Pb-1)+w)/m)
    Winograd tile rows its Pb pooled rows need, advancing only
    row_step = ps*Pb/m tile rows per step, so the pool window never crosses
    a grid step's slab.
    """
    mm = t.m
    B, H, W, _ = x.shape
    g = p.g

    xg, _ = grouped_channel_pad(x, g, p.Cb)
    # a pool with stride > window skips trailing conv rows, so the pooled
    # row plan may read fewer rows than the conv extent — crop, then pad
    used_h = min(H, p.Hp - p.ph_pad)
    xg = xg[:, :used_h]
    xg = jnp.pad(xg, ((0, p.Bp - B), (p.ph_pad, p.Hp - used_h - p.ph_pad),
                      (p.ph_pad, p.Wp - W - p.ph_pad), (0, 0)))

    w_tiles = dma.resolve_slab(w, w_packed, p.weights,
                               lambda w: pack_weights(w, p))
    bias = jnp.zeros((p.Kfull,), x.dtype) if b is None else b
    bg = bias.reshape(g * p.nkb, p.Kb)

    single = p.weights.n_tiles == 1
    row_par = bool(row_parallel) and not single
    kernel = functools.partial(_conv2d_fused_kernel, BT=_coeffs(t.BT),
                               AT=_coeffs(t.AT), relu=relu,
                               checksum=p.checksum, lrn=lrn,
                               pool=pool, row_step=p.row_step,
                               prefetch=weight_prefetch, single=single,
                               row_parallel=row_par)
    out_specs = [pl.BlockSpec((p.Bb, p.rows_out, p.w_out, p.Kfull),
                              lambda bo, i, k, c, bi: (bo, i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(
        (p.Bp, p.npr * p.rows_out, p.w_out, p.Kfull), x.dtype)]
    if p.checksum:
        # per-(batch, row) ABFT verdict: mismatched checksum lanes seen by
        # that block's weight stream (0 everywhere == clean launch)
        out_specs.append(dma.verdict_spec())
        out_shape.append(dma.verdict_shape(p.Bp // p.Bb, p.npr))
    res = pl.pallas_call(
        kernel,
        grid=(p.Bp // p.Bb, p.npr, g * p.nkb, p.ncb, p.Bb),
        in_specs=[
            pl.BlockSpec((p.Bb, p.Hp, p.Wp, p.Cb),
                         lambda bo, i, k, c, bi, nkb=p.nkb, ncb=p.ncb:
                         (bo, 0, 0, (k // nkb) * ncb + c)),
            # tile-packed weights: a single tile rides the BlockSpec
            # pipeline (fetched once, resident); a multi-tile stream stays
            # in ANY space and moves by manual double-buffered DMA
            (dma.single_tile_spec(p.weights) if single
             else pl.BlockSpec(memory_space=pl.ANY)),
            # the whole (g*nkb, Kb) bias stays resident; the kernel picks
            # row k (a (1, Kb) block would break the 8-row tiling rule)
            pl.BlockSpec((g * p.nkb, p.Kb), lambda *_: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((p.Bb, t.n * t.n, p.Rt * p.tw, p.Kb), jnp.float32),
            pltpu.VMEM((p.Bb, g * p.nkb, p.Rt * mm, p.tw * mm, p.Kb),
                       jnp.float32),
            *dma.weight_dma_scratch(p.weights, w_tiles.dtype,
                                    single=single),
        ],
        compiler_params=tpu_compiler_params(
            *dma.grid_semantics(single, row_par),
            vmem_limit_bytes=p.vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(xg, w_tiles, bg)

    out = res[0]
    if pool is not None:
        y = out[:B, :p.ph_out]
    else:
        y = out[:B, :p.out_h, :p.out_w]
    return (y, jnp.sum(res[1])) if p.checksum else y


@functools.partial(jax.jit, static_argnames=("m", "padding", "relu", "groups",
                                             "lrn", "pool", "row_block",
                                             "c_block", "k_block",
                                             "pool_row_block", "batch_block",
                                             "weight_prefetch", "row_parallel",
                                             "checksum", "interpret", "name"))
def conv2d_winograd(x, w, b=None, w_packed=None, *, m: int = 4,
                    padding: str = "SAME", relu: bool = False,
                    groups: int = 1, lrn=None, pool=None, row_block: int = 8,
                    pool_row_block: int | None = None,
                    c_block: int | None = None, k_block: int = 128,
                    batch_block: int = 8, weight_prefetch: bool = True,
                    row_parallel: bool = False, checksum: bool = False,
                    interpret: bool = True, name: str | None = None):
    """x (B,H,W,C); w (r,r,C//groups,K); stride-1 conv via F(m,r) x F(m,r).

    Fused pipeline: raw (halo-padded) feature map slabs stream HBM->VMEM via
    the grid pipeline; tiles, transforms, Winograd GEMMs, channel-block
    accumulation, and the bias+ReLU epilogue all happen in-kernel.  Groups
    fold into the K grid dimension on a group-major channel layout.

    Filter cache + prefetch (paper §3.5): ``batch_block`` images ride the
    innermost grid dimension with the weight tile constant, so each
    transformed filter tile is fetched once per ``batch_block`` images; the
    fetch itself is a manual 2-slot double-buffered async copy — the next
    tile's DMA is in flight while this tile's GEMMs run
    (``weight_prefetch=True``; ``False`` runs the same copies synchronously,
    bit-equal but exposed).  Pass ``w_packed`` — ``pack_weights(w, plan)``
    staged while the previous layer computed — to skip in-trace packing.

    Layer fusion (paper §3.5): with ``lrn`` (an LrnParams-like object) and/or
    ``pool`` ((window, stride)) the cross-channel LRN and VALID max-pool run
    in the kernel epilogue too — the grid is restructured so each row step
    owns a pooled output region (``_conv2d_fused_call``), the k loop
    deposits all g*K channel blocks into a full-channel VMEM scratch (LRN is
    cross-channel, spanning group seams), and only the pooled, normalized
    feature map is ever written to HBM.

    Stream-buffer residency (paper §3.5): like the DLA — whose stream
    buffers hold whole AlexNet feature-map planes in M20K — one full
    (Hp, Wp, c_block) image plane is VMEM-resident per image slot;
    ``c_block=None`` auto-sizes the channel block so the slab fits the VMEM
    budget (at most 128 lanes: the tile planes are stride-m VMEM loads),
    and ``row_block`` tiles the *compute* (tiles/scratch), not input
    residency (see ``conv2d_hbm_bytes``).

    ABFT (``checksum=True``): the packed slab carries one extra bit-pattern
    checksum row per tile (``dma.append_checksum_row``); the kernel verifies
    each resident tile after the DMA slot swap and the call returns
    ``(y, verdict)`` — verdict 0 means every tile streamed intact, > 0
    counts mismatched checksum lanes.  The GEMMs consume the same Cb rows
    either way, so a clean armed launch is bit-identical to unarmed.

    ``name`` names the ``pallas_call``, and so the kernel's HLO instruction
    and its device op in a profile (``conv3_winograd``).
    """
    r = w.shape[0]
    t = winograd_transform(m, r)
    p = plan(x.shape, w.shape, m=m, padding=padding, groups=groups,
             lrn=lrn, pool=pool, row_block=row_block,
             pool_row_block=pool_row_block, c_block=c_block,
             k_block=k_block, batch_block=batch_block, checksum=checksum)
    if p.fused:
        return _conv2d_fused_call(x, w, b, w_packed, t=t, p=p, relu=relu,
                                  lrn=lrn, pool=pool,
                                  weight_prefetch=weight_prefetch,
                                  row_parallel=row_parallel,
                                  interpret=interpret, name=name)
    B, H, W, _ = x.shape
    g = p.g

    # group-major channel layout, raw zero-pad only — no tile gather
    xg, _ = grouped_channel_pad(x, g, p.Cb)
    xg = jnp.pad(xg, ((0, p.Bp - B), (p.ph_pad, p.Hp - H - p.ph_pad),
                      (p.ph_pad, p.Wp - W - p.ph_pad), (0, 0)))

    w_tiles = dma.resolve_slab(w, w_packed, p.weights,
                               lambda w: pack_weights(w, p))
    bias = jnp.zeros((g * p.K,), x.dtype) if b is None else b
    bg = bias.reshape(g, p.K)
    if p.Kp > p.K:
        bg = jnp.pad(bg, ((0, 0), (0, p.Kp - p.K)))
    bg = bg.reshape(g * p.nkb, p.Kb)

    single = p.weights.n_tiles == 1
    row_par = bool(row_parallel) and not single
    kernel = functools.partial(_conv2d_kernel, BT=_coeffs(t.BT),
                               AT=_coeffs(t.AT), relu=relu,
                               checksum=p.checksum,
                               prefetch=weight_prefetch, single=single,
                               row_parallel=row_par)
    out_specs = [pl.BlockSpec((p.Bb, p.Rt * t.m, p.tw * t.m, p.Kb),
                              lambda bo, i, k, c, bi: (bo, i, 0, k))]
    out_shape = [jax.ShapeDtypeStruct(
        (p.Bp, p.thp * t.m, p.tw * t.m, g * p.Kp), x.dtype)]
    if p.checksum:
        out_specs.append(dma.verdict_spec())
        out_shape.append(dma.verdict_shape(p.Bp // p.Bb, p.npr))
    res = pl.pallas_call(
        kernel,
        grid=(p.Bp // p.Bb, p.npr, g * p.nkb, p.ncb, p.Bb),
        in_specs=[
            pl.BlockSpec((p.Bb, p.Hp, p.Wp, p.Cb),
                         lambda bo, i, k, c, bi, nkb=p.nkb, ncb=p.ncb:
                         (bo, 0, 0, (k // nkb) * ncb + c)),
            # tile-packed weights: a single tile rides the BlockSpec
            # pipeline (fetched once, resident); a multi-tile stream stays
            # in ANY space and moves by manual double-buffered DMA
            (dma.single_tile_spec(p.weights) if single
             else pl.BlockSpec(memory_space=pl.ANY)),
            pl.BlockSpec((g * p.nkb, p.Kb), lambda *_: (0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((p.Bb, t.n * t.n, p.Rt * p.tw, p.Kb), jnp.float32),
            *dma.weight_dma_scratch(p.weights, w_tiles.dtype,
                                    single=single),
        ],
        compiler_params=tpu_compiler_params(
            *dma.grid_semantics(single, row_par),
            vmem_limit_bytes=p.vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(xg, w_tiles, bg)

    y = res[0][:B, :p.out_h, :p.out_w]
    if p.Kp > p.K:
        y = y.reshape(B, p.out_h, p.out_w, g, p.Kp)[..., :p.K]
        y = y.reshape(B, p.out_h, p.out_w, g * p.K)
    return (y, jnp.sum(res[1])) if p.checksum else y
