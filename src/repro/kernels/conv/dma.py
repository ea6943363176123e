"""Manual-DMA double-buffered weight pipeline shared by both conv kernels.

Paper §3.5: "filters for the next convolution layer are prefetched while the
current layer is computed" — the DLA's filter cache is fed by a dedicated
data mover that runs *ahead* of the PE array, so the PEs never stall on a
weight fetch.  PR-4's filter-cache grid already reused a weight tile across
``batch_block`` images, but every weight-tile *transition* was still a
synchronous Pallas pipeline fetch serialized against the GEMMs.  This module
replaces that with the DLA's scheme at both levels:

In-kernel (this module + ``winograd.py``/``direct.py``): weights enter the
kernel as a *tile-packed* array left in HBM/ANY memory space — no BlockSpec
pipelining — and move via explicit ``pltpu.make_async_copy`` into a 2-slot
VMEM scratch.  At each tile transition the copy for the *next* tile is
issued into the spare slot before the current step's GEMMs run, and a
transition only ever waits on the copy issued one transition earlier — the
slot swap.  The filter stream is therefore fully double-buffered under MXU
compute; with ``prefetch=False`` the same DMA runs start+wait synchronously
at each transition (the exposed baseline the benchmarks compare against).
Both modes move identical bytes to identical slots, so outputs are
bit-equal (``tests/test_fused_pipeline.py``).

Cross-layer (``WeightStager`` + ``nn/conv.py::pack_conv_weights`` +
``models/alexnet.py``): the host-side packing — Winograd filter transform,
group/channel blocking, tile layout, optional §3.6 BFP quantization — is a
pure function of the layer spec and input *shape*, so layer N+1's slab can
be staged (async-dispatched and cached) while layer N computes.

Tile order contract: tile ``lin = k * ncb + c`` for grid indices
``k in [0, g*nkb)`` (group-major K blocks) and ``c in [0, ncb)`` — exactly
the (k, c) loop order of the shared
``(B/Bb, row blocks, g*K blocks, C blocks, Bb)`` kernel grid, so the
stream advances one tile per (k, c) step and wraps to tile 0 when the row
block (or batch block) increments.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..compat import ARBITRARY, PARALLEL


@dataclass(frozen=True)
class WeightPlan:
    """Blocking of one layer's weight slab into DMA tiles.

    ``spatial`` is the per-tile filter extent — ``(n, n)`` Winograd-domain
    or ``(r, r)`` direct.  The packed array is
    ``(n_tiles, *spatial, Cb, Kb)`` with tile ``lin = k * ncb + c``.

    ``checksum`` arms the ABFT weight stream: every tile carries one extra
    ``Cb`` row holding the bit-pattern column checksum of the rows above it
    (:func:`append_checksum_row`), so ``tile_shape`` grows to
    ``(*spatial, Cb + 1, Kb)`` and the kernels can verify each resident
    tile after the DMA slot swap (:func:`verify_tile_checksum`).
    """
    g: int                  # groups
    nkb: int                # K blocks per group
    ncb: int                # C blocks
    Cb: int                 # channel block
    Kb: int                 # output-channel block
    spatial: tuple          # per-tile filter dims
    checksum: bool = False  # ABFT checksum row appended to every tile

    @property
    def n_tiles(self) -> int:
        return self.g * self.nkb * self.ncb

    @property
    def tile_shape(self) -> tuple:
        return (*self.spatial, self.Cb + (1 if self.checksum else 0),
                self.Kb)


def pack_weight_tiles(wg, plan: WeightPlan):
    """(g, *spatial, ncb*Cb, nkb*Kb) blocked weights -> (n_tiles, *tile).

    The (g, kb, cb) tile index must match the kernel grid's weight walk —
    group ``k // nkb``, K block ``k % nkb``, C block ``c`` — so the packed
    order is (g, nkb, ncb): ``lin = k * ncb + c``.
    """
    g, ncb, Cb, nkb, Kb = plan.g, plan.ncb, plan.Cb, plan.nkb, plan.Kb
    ns = len(plan.spatial)
    assert wg.shape == (g, *plan.spatial, ncb * Cb, nkb * Kb), (
        wg.shape, plan)
    w7 = wg.reshape(g, *plan.spatial, ncb, Cb, nkb, Kb)
    # (g, *spatial, ncb, Cb, nkb, Kb) -> (g, nkb, ncb, *spatial, Cb, Kb)
    perm = (0, ns + 3, ns + 1, *range(1, ns + 1), ns + 2, ns + 4)
    tiles = w7.transpose(perm).reshape(plan.n_tiles, *plan.spatial, Cb, Kb)
    if plan.checksum:
        tiles = append_checksum_row(tiles)
    assert tiles.shape == (plan.n_tiles, *plan.tile_shape)
    return tiles


# ---------------------------------------------------------------------------
# ABFT tile checksums (SDC defense)
# ---------------------------------------------------------------------------
# Checksums are computed over the *bit patterns* of the packed tile, not its
# float values: bitcast each lane to a same-width integer and take the
# wraparound column sum (mod 2**width) along the Cb axis.  A float sum
# cannot guarantee detection of a low-mantissa-bit flip (the delta is
# absorbed by rounding); an integer wraparound sum changes by exactly
# +/- 2**k mod 2**width != 0 for any single flipped bit, so every 1-bit
# corruption anywhere in the tile — weight rows, zero padding, or the
# checksum row itself — is detected, with zero false positives on clean
# data (integer addition is exact and order-independent).
_CHECKSUM_INT = {4: jnp.int32, 2: jnp.int16}


def checksum_int_dtype(dtype):
    """Same-width integer dtype the ABFT checksum runs in."""
    return _CHECKSUM_INT[jnp.dtype(dtype).itemsize]


def tile_checksum(tiles):
    """Bit-pattern column checksum of ``(..., Cb, Kb)`` tiles: bitcast to
    same-width int, wraparound-sum along the Cb axis (sub-32-bit dtypes
    accumulate in int32 and truncate back — consistent at pack and verify
    time, so the comparison is exact)."""
    itype = checksum_int_dtype(tiles.dtype)
    bits = jax.lax.bitcast_convert_type(tiles, itype)
    return jnp.sum(bits.astype(jnp.int32), axis=-2,
                   dtype=jnp.int32).astype(itype)


def append_checksum_row(tiles):
    """Append the checksum as one extra Cb row, bitcast back into the tile
    dtype so the slab stays a single homogeneous array for DMA (the GEMMs
    never read it — kernels slice ``[..., :-1, :]``)."""
    row = tile_checksum(tiles)[..., None, :]
    row = jax.lax.bitcast_convert_type(row, tiles.dtype)
    return jnp.concatenate([tiles, row], axis=-2)


def checksum_mismatches(tile):
    """int32 count of checksum lanes disagreeing with a recomputed sum in
    one ``(..., Cb + 1, Kb)`` checksummed tile (0 == intact)."""
    itype = checksum_int_dtype(tile.dtype)
    want = jax.lax.bitcast_convert_type(tile[..., -1:, :], itype)
    got = tile_checksum(tile[..., :-1, :])[..., None, :]
    return jnp.sum((want != got).astype(jnp.int32), dtype=jnp.int32)


def verify_tile_checksum(sdc_ref, tile):
    """Accumulate the resident tile's checksum mismatches into the
    per-(batch, row) corruption-verdict ref on the shared conv grid.

    Runs once per weight tile (first image slot only), off the GEMM
    critical path — one bitcast + integer reduction per (k, c) transition.
    The verdict block is initialised on the first tile of each (batch,
    row) block, so the output is total mismatched checksum lanes seen by
    that block's weight stream (0 == clean launch).
    """
    k, c, bi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((k == 0) & (c == 0) & (bi == 0))
    def _init():
        sdc_ref[...] = jnp.zeros(sdc_ref.shape, sdc_ref.dtype)

    @pl.when(bi == 0)
    def _count():
        sdc_ref[...] += checksum_mismatches(tile)


def verdict_shape(n_batch_blocks: int, n_row_blocks: int):
    """The per-(batch, row) ABFT verdict output: one int32 per block, in
    trailing (1, 1) dims so its (1, 1, 1, 1) block spans whole dims."""
    return jax.ShapeDtypeStruct((n_batch_blocks, n_row_blocks, 1, 1),
                                jnp.int32)


def verdict_spec():
    """BlockSpec of :func:`verdict_shape` on the shared conv grid."""
    return pl.BlockSpec((1, 1, 1, 1), lambda bo, i, k, c, bi: (bo, i, 0, 0))


def weight_dma_scratch(plan: WeightPlan, dtype, *, single: bool = False):
    """The two scratch allocations the 2-slot pipeline needs, in the order
    the kernels append them: (2-slot VMEM tile buffer, 2 DMA semaphores).
    Single-tile mode keeps the kernel signature (the BlockSpec path never
    touches either) but shrinks the buffer to a degenerate element — a
    full 2-slot copy of the whole resident slab would be dead VMEM."""
    shape = (2,) + ((1,) * len(plan.tile_shape) if single
                    else plan.tile_shape)
    return (pltpu.VMEM(shape, dtype), pltpu.SemaphoreType.DMA((2,)))


def single_tile_spec(plan: WeightPlan):
    """BlockSpec for a single-tile weight stream: the one tile rides the
    ordinary Pallas pipeline at a constant block index (fetched once,
    resident for the launch) instead of the manual-DMA path."""
    nd = len(plan.tile_shape) + 1
    return pl.BlockSpec((1, *plan.tile_shape), lambda *_, nd=nd: (0,) * nd)


def resolve_slab(w, w_packed, plan: WeightPlan, pack_fn):
    """The weight slab a kernel launch will stream: the staged array when
    one was handed in, else packed in-trace — with the one shape check
    that keeps a stale slab from ever reaching the DMA (shared by every
    pallas_call site so the contract cannot diverge between kernels)."""
    w_tiles = pack_fn(w) if w_packed is None else w_packed
    assert w_tiles.shape == (plan.n_tiles, *plan.tile_shape), (
        "staged weight slab does not match this call's plan",
        w_tiles.shape, plan)
    return w_tiles


def grid_semantics(single: bool, row_parallel: bool = False):
    """Dimension semantics for the shared (batch, rows, k, c, images) conv
    grid under the DMA weight stream: the stream restarts per batch-outer
    block, so the batch dim is always parallel; the slot state spanning
    the row/k/c walk keeps those dims arbitrary on multi-tile launches —
    unless ``row_parallel`` restarts the stream per *row block* too
    (:func:`stream_positions`), in which case no DMA state crosses row
    steps and the row dim is freed.  A single-tile launch (no slot state
    at all) frees the row dim unconditionally.  The image-slot dim stays
    arbitrary (filter-cache accumulators).
    """
    return (PARALLEL, PARALLEL if (single or row_parallel) else ARBITRARY,
            ARBITRARY, ARBITRARY, ARBITRARY)


def stream_positions(ib, k, c, *, npr: int, nk: int, nc: int,
                     row_restart: bool = False):
    """Weight-stream coordinates of one grid step.

    The stream is self-contained *per batch-outer block*: the transition
    counter restarts at every filter-cache generation, so the batch grid
    dimension carries no cross-block DMA state and can stay ``parallel``
    (each core's slice warms up its own stream; one exposed warmup tile
    per generation instead of per launch).

    ``row_restart`` applies the same restart at every *row block*: the
    transition counter (and with it the slot parity, which always starts
    at slot 0 for a fresh generation — the parity bookkeeping that made
    the global counter necessary when a generation spanned odd-length
    row-block streams) becomes ``k * nc + c``, each row block warms up its
    own tile-0 copy and drains fully by its last transition, so no DMA
    slot state crosses row steps and the row grid dimension can be marked
    ``parallel`` (:func:`grid_semantics`).  Cost: one exposed warmup tile
    per (batch-outer, row) generation instead of per batch-outer block —
    the trade the autotuner measures (``core/autotune.py``).

    Returns ``(trans, lin, lin_next, last)``: the in-generation transition
    counter (slot parity rides this, not ``lin`` — the per-row-block
    stream length ``nk*nc`` may be odd when the generation spans row
    blocks), the current/next tile indices (the stream wraps to tile 0
    when the row block advances), and whether this is the generation's
    final transition (no further copy to issue).
    """
    lin = k * nc + c
    lin_next = jax.lax.rem(lin + 1, nk * nc)
    if row_restart:
        return lin, lin, lin_next, lin + 1 >= nk * nc
    trans = (ib * nk + k) * nc + c
    last = trans + 1 >= npr * nk * nc
    return trans, lin, lin_next, last


def weight_stream_transition(w_tiles, wbuf, sem, *, trans, lin, lin_next,
                             last, prefetch: bool):
    """Run the 2-slot DMA schedule at one weight-tile transition.

    ``prefetch=True`` (double-buffered): the very first transition warms up
    its own copy; every non-final transition issues the *next* tile's copy
    into the spare slot before the caller's GEMMs; the only wait is on the
    copy issued one transition earlier (the slot swap), so steady-state
    fetches overlap MXU compute entirely.  ``prefetch=False`` start+waits
    the same copy synchronously — same bytes, same slots, bit-equal output,
    but every fetch is exposed.  Call under ``pl.when(bi == 0)`` (the first
    image slot of the tile); later image slots read the resident slot.
    """
    slot = jax.lax.rem(trans, 2)
    if prefetch:
        @pl.when(trans == 0)
        def _warmup():
            pltpu.make_async_copy(w_tiles.at[lin], wbuf.at[slot],
                                  sem.at[slot]).start()

        @pl.when(jnp.logical_not(last))
        def _issue_next():
            nxt = jax.lax.rem(trans + 1, 2)
            pltpu.make_async_copy(w_tiles.at[lin_next], wbuf.at[nxt],
                                  sem.at[nxt]).start()

        pltpu.make_async_copy(w_tiles.at[lin], wbuf.at[slot],
                              sem.at[slot]).wait()
    else:
        cp = pltpu.make_async_copy(w_tiles.at[lin], wbuf.at[slot],
                                   sem.at[slot])
        cp.start()
        cp.wait()


def current_slot(trans):
    """VMEM slot holding the resident tile for transition counter ``trans``
    (valid at every image slot of the tile, not just the transition step)."""
    return jax.lax.rem(trans, 2)


def fetch_weight_tile(w_tiles, wbuf, sem, *, prefetch: bool, single: bool,
                      row_parallel: bool = False):
    """Drive the weight stream for one step of the shared
    ``(B/Bb, row blocks, g*K blocks, C blocks, Bb)`` conv grid and return
    the resident (raw-dtype) tile — the whole per-step bookkeeping both
    kernels share: stream coordinates from the grid ids, the 2-slot
    transition on the first image slot of each tile, the slot read
    elsewhere.

    ``single`` (static): the stream has exactly one tile, so there is no
    rotation to drive — the host passed the tile through a constant-index
    BlockSpec instead of the ANY-space ref (``single_tile_spec``), Pallas's
    pipeline fetches it once and keeps it resident (its usual elision for
    an unchanged block index), and the grid keeps its parallel batch/row
    semantics because no DMA slot state spans steps.  ``wbuf``/``sem`` are
    unused in that mode.

    ``row_parallel`` (static): restart the stream per row block
    (``stream_positions(row_restart=True)``) so the row grid dimension can
    run ``parallel`` — same tiles, same slots, bit-equal output, one extra
    exposed warmup tile per row block.
    """
    if single:
        return w_tiles[0]

    trans, lin, lin_next, last = stream_positions(
        pl.program_id(1), pl.program_id(2), pl.program_id(3),
        npr=pl.num_programs(1), nk=pl.num_programs(2),
        nc=pl.num_programs(3), row_restart=row_parallel)

    @pl.when(pl.program_id(4) == 0)
    def _fetch():
        weight_stream_transition(w_tiles, wbuf, sem, trans=trans, lin=lin,
                                 lin_next=lin_next, last=last,
                                 prefetch=prefetch)

    return wbuf[current_slot(trans)]


# ---------------------------------------------------------------------------
# cross-layer staging
# ---------------------------------------------------------------------------
def _has_tracer(tree) -> bool:
    return any(isinstance(leaf, jax.core.Tracer)
               for leaf in jax.tree_util.tree_leaves(tree))


class WeightStager:
    """Cross-layer weight staging: dispatch layer N+1's (pure, jittable)
    weight packing while layer N computes, and cache the packed slab.

    JAX dispatch is asynchronous, so ``stage`` returns immediately — the
    packing work overlaps whatever device work is already queued (the
    current layer's conv).  Keys are caller-chosen (AlexNet uses layer
    names); a stager is bound to one parameter set — reuse it across
    forward passes of the same params (serving) and the slab packs once,
    the host-level twin of the in-kernel filter cache.

    Tracer-safe: under ``jax.jit`` the packed value would be a tracer, so
    staging computes inline and caches nothing (XLA already schedules the
    inlined pack; caching tracers across traces would be unsound).

    ``verify=True`` arms slab-integrity checking on the cache-hit path:
    instead of trusting the cache key, a hit whose value carries a
    pack-time fingerprint (``nn/conv.py::SlabFingerprint``) is re-verified
    — shape, dtype, content crc32, and (when the caller passes ``expect``)
    the pack context the slab was built under.  A mismatch counts in
    ``integrity_failures``, evicts the entry, and repacks through the miss
    path — so a corrupted cached slab, or a stale one reused after the
    layer was repacked under different fusion flags, never reaches a
    kernel.
    """

    def __init__(self, *, verify: bool = False):
        self._cache: dict = {}
        self.hits = 0
        self.misses = 0
        self.verify = verify
        self.integrity_failures = 0

    @staticmethod
    def _intact(val, expect) -> bool:
        """Duck-typed fingerprint check: values without one (plain arrays,
        slabs packed unfingerprinted) have nothing to verify against."""
        fp = getattr(val, "fingerprint", None)
        return fp is None or fp.matches(val, expect=expect)

    def stage(self, key, fn, *args, expect=None, **kwargs):
        """Compute (or recall) ``fn(*args)`` for ``key``; returns the value."""
        if key in self._cache:
            val = self._cache[key]
            if not self.verify or self._intact(val, expect):
                self.hits += 1
                return val
            self.integrity_failures += 1
            del self._cache[key]        # fall through: repack from pristine
        val = fn(*args, **kwargs)
        self.misses += 1
        if key is not None and not _has_tracer((args, kwargs, val)):
            self._cache[key] = val
        return val

    def get(self, key, default=None):
        if key in self._cache:
            self.hits += 1
            return self._cache[key]
        return default

    def clear(self):
        self._cache.clear()
