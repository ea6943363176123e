"""Stream-buffered Pallas direct conv kernel — the paper's non-Winograd
first-layer datapath (§3.3, §3.5), generalized.

The DLA runs AlexNet's 11x11 stride-4 conv1 through the *same* stream-
buffer pipeline as the Winograd layers: the PE array is fed raw feature-map
slabs from on-chip buffers and the filters come from the filter cache —
no layer ever detours through external memory just because its geometry is
not F(4,3)-shaped.  This kernel is that datapath on TPU: arbitrary kernel
size, stride, groups, and SAME/VALID padding, with the identical fused
bias + ReLU + cross-channel-LRN + max-pool epilogue (``epilogue.py``,
shared with the Winograd kernel) and the identical
(B/Bb, row blocks, g*K blocks, C blocks, Bb) filter-cache grid.

Strided layers run as stride-1 convs by space-to-depth: the wrapper folds
each s x s pixel block into s*s*C channels and the filters into
ceil(r/s) x ceil(r/s) taps over those channels (zero taps pad r up to a
multiple of s).  conv1's 11x11 stride 4 over 3 channels becomes a 3x3
stride-1 conv over 48 channels — no strided value slices (Mosaic takes
only stride-1 ones) and no 3-lane feature map padded to 128 lanes in VMEM.

Compute shape: the conv is phrased as r^2 MXU GEMMs per grid step, one per
filter tap: (rows*cols, Cb) @ (Cb, Kb) over the tap's shifted window of
the VMEM-resident slab.

Weight path (§3.5 filter prefetch, shared machinery in ``dma.py``): the
filters arrive *tile-packed* in an ANY/HBM-space ref and move by explicit
``pltpu.make_async_copy`` into a 2-slot VMEM scratch — at each (k, c) tile
transition the next tile's copy is issued before this step's GEMMs and the
only wait is the slot swap, so the weight stream is double-buffered under
MXU compute.  ``pack_weights``/``weight_plan`` expose the packing as a pure
function of shapes so a model can stage layer N+1's slab while layer N
computes (``nn/conv.py::pack_conv_weights``).

Dataflow per grid step (image slot ``bi`` of the ``batch_block`` in
flight):

* the halo-padded input plane (Bb, Hp, Wp, Cb) is VMEM-resident; the step
  loads its ``in_rows = Rc - 1 + r`` rows tap by tap (no im2col tensor in
  HBM),
* channel blocks accumulate into a per-image VMEM scratch
  (``acc_ref[bi]``, the PE daisy-chain),
* the last c block deposits bias+ReLU'd channels into the full-channel
  ``y_ref[bi]`` scratch, and the last (k, c) step runs LRN + pool in VMEM
  and writes only the pooled map (§3.5 — the conv-resolution feature map
  never reaches HBM).

With ``pool`` set, each row step owns ``Pb`` pooled rows: it computes the
``Rc = ps*(Pb-1)+pwin`` conv rows those need but advances only
``ps*Pb`` input rows, keeping the pool's output-side halo in VMEM (the
direct analogue of the Winograd kernel's tile-aligned pooled-row blocks —
no tile-alignment constraint here, since rows are computed directly).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.winograd import LANES, SUBLANES, auto_pool_rows
from ..compat import tpu_compiler_params
from . import dma
from .epilogue import F32_DOT, VMEM_NEED_MAX, batch_blocks, channel_blocks, \
    fused_epilogue, grouped_channel_pad, k_blocks, vmem_limit, vmem_need


def same_pad(extent: int, r: int, stride: int) -> tuple[int, int, int]:
    """(out, pad_lo, pad_hi) for SAME padding, matching lax.conv semantics."""
    out = -(-extent // stride)
    total = max((out - 1) * stride + r - extent, 0)
    return out, total // 2, total - total // 2


def pads(extent: int, r: int, stride: int, padding) -> tuple[int, int, int]:
    """(out, pad_lo, pad_hi) for "SAME", "VALID" or explicit ``(lo, hi)``
    padding of one spatial axis."""
    if padding == "SAME":
        return same_pad(extent, r, stride)
    lo, hi = (0, 0) if padding == "VALID" else padding
    return (extent + lo + hi - r) // stride + 1, lo, hi


@dataclass(frozen=True)
class DirectPlan:
    """Host-side launch plan: every derived extent of one kernel call.

    Pure function of shapes + static params (``plan``), so the weight
    packing (``pack_weights``) can run ahead of the input tensor — the
    cross-layer staging hook.  Extents past the padding are those of the
    stride-1 space-to-depth conv the kernel runs (``s`` x ``s`` pixel
    blocks; ``s == 1`` leaves the layer as it is).
    """
    r: int                  # filter taps after space-to-depth
    s: int                  # layer stride == space-to-depth block
    r_in: int               # the layer's own filter size
    g: int
    C: int                  # channels per group (s*s x the layer's)
    K: int                  # out channels per group
    out_h: int
    out_w: int
    wc: int                 # computed cols: out_w up to the 8-row tile
    ph_lo: int              # padding of the layer's input, in pixels
    pw_lo: int
    ph_out: int             # pooled output rows (== out_h when no pool)
    pw_out: int
    Rc: int                 # conv rows each row step computes
    step_in: int            # input rows advanced per row step
    in_rows: int            # raw rows read per step (with halo)
    npr: int                # row steps
    rows_out: int
    w_out: int
    Hp: int
    Wp: int
    Bb: int
    Bp: int
    Cb: int
    Cp: int
    ncb: int
    Kb: int
    nkb: int
    checksum: bool = False  # ABFT checksum row on every weight tile
    residual: bool = False  # a (Bb, Rc, wc, Kb) residual tile per K block

    @property
    def Kfull(self) -> int:
        return self.g * self.K

    @property
    def weights(self) -> dma.WeightPlan:
        return dma.WeightPlan(g=self.g, nkb=self.nkb, ncb=self.ncb,
                              Cb=self.Cb, Kb=self.Kb,
                              spatial=(self.r, self.r),
                              checksum=self.checksum)

    def _buffers(self) -> tuple[list, list]:
        """(pipelined, scratch) VMEM block shapes of one launch."""
        tile = (1, *self.weights.tile_shape)
        pipelined = [(self.Bb, self.Hp, self.Wp, self.Cb),
                     (self.g * self.nkb, self.Kb),
                     (self.Bb, self.rows_out, self.w_out, self.Kfull)]
        scratch = [(self.Bb, self.Rc, self.wc, self.Kb),
                   (self.Bb, self.g * self.nkb, self.Rc, self.wc, self.Kb)]
        if self.weights.n_tiles == 1:
            pipelined.append(tile)
        else:
            scratch.append((2, *tile[1:]))
        if self.residual:
            pipelined.append((self.Bb, self.Rc, self.wc, self.Kb))
        return pipelined, scratch

    @property
    def vmem_need_bytes(self) -> int:
        return vmem_need(*self._buffers())

    @property
    def vmem_limit_bytes(self) -> int:
        return vmem_limit(*self._buffers())


def plan(x_shape, w_shape, *, stride: int = 1, padding="SAME",
         pool=None, groups: int = 1, row_block: int = 8,
         pool_row_block: int | None = None, c_block: int | None = None,
         k_block: int = 128, batch_block: int = 8,
         checksum: bool = False, residual: bool = False) -> DirectPlan:
    """Derive the full launch plan from shapes + static params.

    ``padding`` is "SAME", "VALID" or explicit ``(lo, hi)`` pads on each
    spatial side.  The batch block is ``batch_block`` unless the launch's
    buffers would not fit VMEM with it: a plane of at most 128 channels
    cannot split into channel blocks (``auto_c_block``), so it is halved
    until they fit (ResNet's 224 px stem at 8 images).
    """
    r_in, s, g = w_shape[0], stride, groups
    assert w_shape[0] == w_shape[1], "square filters only"
    B, H, W, Ct = x_shape
    Kt = w_shape[-1]
    assert Ct % g == 0 and Kt % g == 0 and w_shape[2] == Ct // g, (
        "grouped conv shape mismatch")
    K = Kt // g
    out_h, ph_lo, _ = pads(H, r_in, s, padding)
    out_w, pw_lo, _ = pads(W, r_in, s, padding)
    assert out_h >= 1 and out_w >= 1, (H, W, r_in, s, padding)
    r, C = -(-r_in // s), s * s * (Ct // g)     # space-to-depth geometry

    Bb, Bp = batch_blocks(B, batch_block)
    if pool is not None:
        pwin, ps = pool
        ph_out = (out_h - pwin) // ps + 1
        pw_out = (out_w - pwin) // ps + 1
        assert ph_out >= 1 and pw_out >= 1, (
            f"pool {pool} larger than conv output {out_h}x{out_w}")
        if pool_row_block is None:
            # own the whole pooled extent when the epilogue scratch fits —
            # one row step, so grouped layers never re-fetch their slab
            Pb = auto_pool_rows(ph_out, pwin, ps, cols=out_w, kfull=g * K,
                                batch=Bb)
        else:
            Pb = min(pool_row_block, ph_out)
        Rc = ps * (Pb - 1) + pwin               # conv rows each step owns
        step_in = ps * Pb                       # input rows advanced per step
        npr = -(-ph_out // Pb)
        rows_out, w_out = Pb, pw_out
    else:
        ph_out, pw_out = out_h, out_w
        Rc = min(row_block, out_h)
        step_in = Rc
        npr = -(-out_h // Rc)
        rows_out, w_out = Rc, out_w
    in_rows = Rc - 1 + r                        # raw rows per step (w/ halo)
    Hp = (npr - 1) * step_in + in_rows
    # the step computes whole 8-row tiles of columns, so a tap's (Rc, wc,
    # Cb) window flattens into its GEMM operand without a relayout; the
    # extra columns read zero padding and are never stored
    wc = -(-out_w // SUBLANES) * SUBLANES
    Wp = wc - 1 + r

    Cb = channel_blocks(C, c_block, Hp, Wp, Bb, groups=g)
    Kb = k_blocks(K, k_block)
    if c_block is None and g == 1 and Kb % LANES and Cb < C:
        # a K block narrower than the lanes cannot be a slot of the 2-slot
        # DMA buffer (Mosaic slices it whole-tile only), so every channel
        # stays resident and the filters are one tile (ResNet's 256 -> 64
        # 1x1 convs)
        Cb = C
    Cp = C + (-C) % Cb
    p = DirectPlan(r=r, s=s, r_in=r_in, g=g, C=C, K=K, out_h=out_h,
                   out_w=out_w, wc=wc, ph_lo=ph_lo, pw_lo=pw_lo,
                   ph_out=ph_out, pw_out=pw_out, Rc=Rc, step_in=step_in,
                   in_rows=in_rows, npr=npr, rows_out=rows_out,
                   w_out=w_out, Hp=Hp, Wp=Wp, Bb=Bb, Bp=Bp, Cb=Cb,
                   Cp=Cp, ncb=Cp // Cb, Kb=Kb, nkb=K // Kb,
                   checksum=checksum, residual=residual)
    while p.Bb > 1 and p.vmem_need_bytes > VMEM_NEED_MAX:
        Bb, Bp = batch_blocks(B, -(-p.Bb // 2))
        p = replace(p, Bb=Bb, Bp=Bp)
    return p


def _filters_to_depth(w, p: DirectPlan):
    """(r_in, r_in, C0, g*K) -> (r, r, s*s*C0, g*K): zero taps pad the
    filter to r*s, then each s x s tap block folds into the channel dim in
    the (row phase, col phase, channel) order of ``_input_to_depth``."""
    s, r = p.s, p.r
    if s == 1:
        return w
    pad = r * s - p.r_in
    w = jnp.pad(w, ((0, pad), (0, pad), (0, 0), (0, 0)))
    c0, kt = w.shape[2], w.shape[3]
    w = w.reshape(r, s, r, s, c0, kt).transpose(0, 2, 1, 3, 4, 5)
    return w.reshape(r, r, s * s * c0, kt)


def _input_to_depth(x, p: DirectPlan):
    """(B, Hp*s, Wp*s, g*C0) padded pixels -> (B, Hp, Wp, g*s*s*C0),
    group-major so each group's folded channels stay contiguous."""
    s = p.s
    if s == 1:
        return x
    B, H, W, Ct = x.shape
    c0 = Ct // p.g
    x = x.reshape(B, H // s, s, W // s, s, p.g, c0)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6)
    return x.reshape(B, H // s, W // s, p.g * s * s * c0)


def pack_weights(w, p: DirectPlan):
    """(r_in, r_in, C0, g*K) -> (n_tiles, r, r, Cb, Kb) DMA tile layout."""
    r, g, C, K = p.r, p.g, p.C, p.K
    w = _filters_to_depth(w, p)
    wg = jnp.moveaxis(w.reshape(r, r, C, g, K), 3, 0)       # (g, r, r, C, K)
    if p.Cp > C:
        wg = jnp.pad(wg, ((0, 0), (0, 0), (0, 0), (0, p.Cp - C), (0, 0)))
    return dma.pack_weight_tiles(wg, p.weights)


def _direct_kernel(x_ref, w_tiles, b_ref, *refs, residual: bool,
                   relu: bool, checksum: bool, lrn, pool, step_in: int,
                   prefetch: bool, single: bool, row_parallel: bool):
    res_ref = None
    if residual:
        res_ref, *refs = refs
    out_ref, *refs = refs
    if checksum:
        sdc_ref, acc_ref, y_ref, wbuf, sem = refs
    else:
        acc_ref, y_ref, wbuf, sem = refs
    _, Rc, wo, Kb = acc_ref.shape
    ib = pl.program_id(1)
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    c = pl.program_id(3)
    nc = pl.num_programs(3)
    bi = pl.program_id(4)                           # filter-cache image slot
    w = dma.fetch_weight_tile(w_tiles, wbuf, sem, prefetch=prefetch,
                              single=single, row_parallel=row_parallel)
    if checksum:
        # ABFT: verify the resident tile's checksum row, then strip it —
        # the GEMMs consume the same Cb rows as an unarmed launch
        dma.verify_tile_checksum(sdc_ref, w)
        w = w[..., :-1, :]
    w = w.astype(jnp.float32)

    @pl.when(c == 0)
    def _init():
        acc_ref[bi] = jnp.zeros(acc_ref.shape[1:], acc_ref.dtype)

    r, _, Cb, _ = w.shape
    row0 = ib * step_in
    acc = jnp.zeros((Rc * wo, Kb), jnp.float32)
    for di in range(r):
        for dj in range(r):
            # one (Rc*wo, Cb) @ (Cb, Kb) MXU GEMM per filter tap
            tap = x_ref[bi, pl.ds(row0 + di, Rc), pl.ds(dj, wo), :]
            acc += jnp.dot(tap.reshape(Rc * wo, Cb).astype(jnp.float32),
                           w[di, dj], precision=F32_DOT,
                           preferred_element_type=jnp.float32)
    acc_ref[bi] += acc.reshape(Rc, wo, Kb)          # one scratch RMW per step

    @pl.when(c == nc - 1)
    def _store_kblock():
        y = acc_ref[bi] + b_ref[pl.ds(k, 1), :].astype(jnp.float32)
        if res_ref is not None:                     # conv + b + shortcut
            y = y + res_ref[bi].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        y_ref[bi, k] = y

    @pl.when((c == nc - 1) & (k == nk - 1))
    def _epilogue():
        fused_epilogue(y_ref, out_ref, bi, lrn, pool)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "relu",
                                             "groups", "lrn", "pool",
                                             "row_block", "pool_row_block",
                                             "c_block", "k_block",
                                             "batch_block", "weight_prefetch",
                                             "row_parallel", "checksum",
                                             "interpret", "name"))
def conv2d_direct(x, w, b=None, w_packed=None, residual=None, *,
                  stride: int = 1, padding="SAME", relu: bool = False,
                  groups: int = 1,
                  lrn=None, pool=None, row_block: int = 8,
                  pool_row_block: int | None = None,
                  c_block: int | None = None, k_block: int = 128,
                  batch_block: int = 8, weight_prefetch: bool = True,
                  row_parallel: bool = False, checksum: bool = False,
                  interpret: bool = True, name: str | None = None):
    """x (B,H,W,C); w (r,r,C//groups,K); any r/stride/groups, fused layer.

    Same contract as the Winograd kernel (``winograd.conv2d_winograd``):
    optional bias ``b (K,)``, fused ``relu``, grouped conv on the
    group-major channel layout, and the in-VMEM ``lrn``/``pool`` epilogue —
    so ``nn.conv.dispatch_conv`` can send *any* ConvSpec here and every
    AlexNet layer (conv1's 11x11 stride 4 included, as a space-to-depth
    stride-1 conv) runs fully in-VMEM on the ``pallas`` route.

    Weight stream: ``pack_weights(w, plan(...))`` tiles the filters; the
    kernel double-buffers them HBM->VMEM by manual async copy
    (``weight_prefetch=True``; ``False`` runs the same copies synchronously
    — bit-equal, every fetch exposed).  Pass ``w_packed`` (a slab staged by
    ``nn.conv.pack_conv_weights`` while the previous layer computed) to
    skip the in-trace packing.

    ``c_block=None`` auto-sizes the channel block so the whole resident
    (batch_block, Hp, Wp, Cb) input block fits the VMEM slab budget, and
    ``pool_row_block=None`` grows the pooled-row block to the whole pooled
    extent while the epilogue scratch fits — AlexNet layers keep all of C
    resident and (grouped layers included, whose slab block index cycles
    per row block) stream the slab HBM->VMEM once per image.

    ``row_parallel`` restarts the DMA weight stream per row block so the
    row grid dimension runs ``parallel`` instead of ``arbitrary``
    (bit-equal; one extra exposed warmup tile per row block) — the
    row-parallel regime the autotuner searches.

    ABFT (``checksum=True``): the packed slab carries one extra bit-pattern
    checksum row per tile; the kernel verifies each resident tile after the
    DMA slot swap and the call returns ``(y, verdict)`` — verdict 0 means
    every tile streamed intact.  Clean armed output is bit-identical to
    unarmed (the GEMMs read the same Cb rows either way).

    ``name`` names the ``pallas_call``, and so the kernel's HLO instruction
    and its device op in a profile (``conv1_direct``).

    ``residual`` (B, out_h, out_w, K), a residual block's shortcut, is
    added in the epilogue after the bias and before the ReLU, each K
    block's tile read from a second VMEM operand.  No LRN or pool then.
    Without it the kernel is traced as it always was.
    """
    p = plan(x.shape, w.shape, stride=stride, padding=padding, pool=pool,
             groups=groups, row_block=row_block,
             pool_row_block=pool_row_block, c_block=c_block,
             k_block=k_block, batch_block=batch_block, checksum=checksum,
             residual=residual is not None)
    B, H, W, _ = x.shape
    assert residual is None or (
        pool is None and lrn is None
        and residual.shape == (B, p.out_h, p.out_w, p.Kfull)), (
        "a residual is added to the conv's own output, before any pool",
        None if residual is None else residual.shape, pool, lrn)
    s, g = p.s, p.g

    # strided convs can leave trailing rows/cols no output window reads —
    # crop them before padding up to the slab extent; a pool with
    # stride > window additionally skips trailing *conv* rows, so the row
    # plan may read fewer rows than the conv extent (Hp < padded H)
    used_h = min(H, s * (p.out_h - 1) + p.r_in - p.ph_lo, s * p.Hp - p.ph_lo)
    used_w = min(W, s * (p.out_w - 1) + p.r_in - p.pw_lo)
    xg = jnp.pad(x[:, :used_h, :used_w],
                 ((0, p.Bp - B), (p.ph_lo, s * p.Hp - used_h - p.ph_lo),
                  (p.pw_lo, s * p.Wp - used_w - p.pw_lo), (0, 0)))
    xg, _ = grouped_channel_pad(_input_to_depth(xg, p), g, p.Cb)
    w_tiles = dma.resolve_slab(w, w_packed, p.weights,
                               lambda w: pack_weights(w, p))
    bias = jnp.zeros((p.Kfull,), x.dtype) if b is None else b
    bg = bias.reshape(g * p.nkb, p.Kb)

    single = p.weights.n_tiles == 1
    row_par = bool(row_parallel) and not single
    kernel = functools.partial(_direct_kernel, residual=p.residual,
                               relu=relu, checksum=p.checksum, lrn=lrn,
                               pool=pool, step_in=p.step_in,
                               prefetch=weight_prefetch,
                               single=single, row_parallel=row_par)
    out_specs = [pl.BlockSpec((p.Bb, p.rows_out, p.w_out, p.Kfull),
                              lambda bo, i, k, c, bi: (bo, i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct(
        (p.Bp, p.npr * p.rows_out, p.w_out, p.Kfull), x.dtype)]
    if p.checksum:
        out_specs.append(dma.verdict_spec())
        out_shape.append(dma.verdict_shape(p.Bp // p.Bb, p.npr))
    in_specs = [
        pl.BlockSpec((p.Bb, p.Hp, p.Wp, p.Cb),
                     lambda bo, i, k, c, bi, nkb=p.nkb, ncb=p.ncb:
                     (bo, 0, 0, (k // nkb) * ncb + c)),
        # tile-packed weights: a single tile rides the BlockSpec pipeline
        # (fetched once, resident); a multi-tile stream stays in ANY space
        # and moves by manual double-buffered DMA
        (dma.single_tile_spec(p.weights) if single
         else pl.BlockSpec(memory_space=pl.ANY)),
        # the whole (g*nkb, Kb) bias stays resident; the kernel picks row
        # k (a (1, Kb) block would break the 8-row tiling rule)
        pl.BlockSpec((g * p.nkb, p.Kb), lambda *_: (0, 0)),
    ]
    operands = [xg, w_tiles, bg]
    if p.residual:
        # padded to the scratch's (Rc, wc) tiles: each (row step, K block)
        # reads its shortcut tile once, whole, beside the accumulator
        operands.append(jnp.pad(residual.astype(x.dtype), (
            (0, p.Bp - B), (0, p.npr * p.Rc - p.out_h),
            (0, p.wc - p.out_w), (0, 0))))
        in_specs.append(pl.BlockSpec((p.Bb, p.Rc, p.wc, p.Kb),
                                     lambda bo, i, k, c, bi: (bo, i, 0, k)))
    res = pl.pallas_call(
        kernel,
        grid=(p.Bp // p.Bb, p.npr, g * p.nkb, p.ncb, p.Bb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((p.Bb, p.Rc, p.wc, p.Kb), jnp.float32),
            pltpu.VMEM((p.Bb, g * p.nkb, p.Rc, p.wc, p.Kb), jnp.float32),
            *dma.weight_dma_scratch(p.weights, w_tiles.dtype,
                                    single=single),
        ],
        compiler_params=tpu_compiler_params(
            *dma.grid_semantics(single, row_par),
            vmem_limit_bytes=p.vmem_limit_bytes),
        interpret=interpret,
        name=name,
    )(*operands)

    out = res[0]
    y = out[:B, :p.ph_out] if pool is not None else out[:B, :p.out_h]
    return (y, jnp.sum(res[1])) if p.checksum else y
