"""Unified conv-layer dispatch: declarative ConvSpec -> one entry point.

Models declare each conv *layer* as a :class:`ConvSpec` — kernel geometry,
groups, fusion flags (bias, ReLU, cross-channel LRN, max-pool), route — and
call :func:`dispatch_conv`; all routing policy — Winograd eligibility,
Pallas vs jnp, direct fallback, grouped batching — lives here instead of
ad-hoc per-model branching.

Routes
------
``direct``    ``lax.conv_general_dilated`` (any kernel/stride; groups via
              ``feature_group_count``), bias/ReLU/LRN/pool as epilogue.
``winograd``  pure-jnp F(m,r) x F(m,r) path (differentiable; training).
``pallas``    stream-buffered Pallas kernels (in-kernel tiling,
              channel-block reduction, filter-cache batch grid, fused
              bias+ReLU+LRN+pool epilogue; inference).
``auto``      ``winograd`` when eligible, else ``direct``.

Winograd math requires stride 1 and a 3x3 kernel (the paper's F(4,3)
layers).  The ``pallas`` route serves *every* geometry: Winograd-eligible
specs hit the Winograd-domain kernel, everything else (AlexNet's 11x11
stride-4 conv1, the 5x5 conv2, pointwise, ...) hits the strided direct
kernel — like the paper's DLA, whose stream buffers feed both the Winograd
PEs and the non-Winograd first layer (§3.3/§3.5).  Only the pure-jnp
``winograd`` route still falls back to ``direct`` on ineligible specs.
:func:`resolve_kernel` exposes the fully resolved datapath
(``pallas-winograd`` / ``pallas-direct`` / ``winograd`` / ``direct``) so
serving can log per-layer routes instead of degrading silently.

Layer-level fusion (paper §3.5): with ``fuse_lrn`` / ``fuse_pool`` the
post-conv stages run inside the conv call — in VMEM on the Pallas route, so
the full-resolution feature map never round-trips HBM between conv, norm,
and pool.  All routes share one fused signature and stay numerically
interchangeable against the unfused conv -> lrn -> maxpool reference
(``repro.nn.pooling``).

Weight staging (paper §3.5 filter prefetch, cross-layer level): the Pallas
kernels take their filters as a *tile-packed slab* that a model can build
ahead of time — :func:`pack_conv_weights` is a pure function of the layer
spec and input shape, so layer N+1's slab (Winograd-transformed, blocked,
optionally §3.6 BFP-quantized) can be dispatched while layer N computes.
:func:`dispatch_conv` accepts the staged slab (``w_packed``) plus a
``prefetch_next`` callable it invokes right after issuing the conv — the
hook a model uses to stage the *next* layer's weights behind the current
layer's compute (see ``models/alexnet.py`` and
``kernels/conv/dma.py::WeightStager``).
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bfp
from ..core.winograd import conv2d_winograd
from ..kernels.conv import direct as _direct_k
from ..kernels.conv import dma as _dma
from ..kernels.conv import winograd as _winograd_k
from ..kernels.conv.ops import conv2d as pallas_conv2d
from ..kernels.conv.ops import conv2d_direct as pallas_conv2d_direct
from ..kernels.conv.ref import conv2d_ref
from .pooling import LrnParams, apply_epilogue, pooled_hw

ROUTES = ("auto", "direct", "winograd", "pallas")

# fully resolved datapaths reported by resolve_kernel
KERNELS = ("direct", "winograd", "pallas-winograd", "pallas-direct")

# sentinel distinguishing "knob not passed" from an explicit None (= auto)
UNSET = object()


@dataclass(frozen=True)
class ConvPlan:
    """A per-layer launch plan over the real kernel knobs — what the
    measured autotuner (``core/autotune.py``, the paper's §4 DSE run live)
    searches, persists, and feeds back into :func:`dispatch_conv`.

    The defaults ARE the repo's default launch configuration: a
    ``ConvPlan()`` reproduces exactly what ``dispatch_conv`` runs when no
    knob is passed, so the default plan is always a member of any
    candidate set and "tuned" can never regress it.

    ``route`` optionally overrides the spec's route preference (a
    :data:`ROUTES` member); ``None`` keeps the spec's own routing.  All
    other fields mirror the kernel knobs: ``c_block``/``pool_row_block``
    ``None`` means auto-size against the VMEM budget
    (``auto_c_block``/``auto_pool_rows``), ``row_parallel`` restarts the
    DMA weight stream per row block so the row grid dimension runs
    ``parallel`` (bit-equal; one extra exposed warmup tile per row block).
    """
    batch_block: int = 8
    k_block: int = 128
    c_block: int | None = None
    pool_row_block: int | None = None
    weight_prefetch: bool = True
    row_parallel: bool = False
    route: str | None = None

    def __post_init__(self):
        assert self.route is None or self.route in ROUTES, self.route
        assert self.batch_block >= 1 and self.k_block >= 1

    def to_dict(self) -> dict:
        return {"batch_block": self.batch_block, "k_block": self.k_block,
                "c_block": self.c_block,
                "pool_row_block": self.pool_row_block,
                "weight_prefetch": self.weight_prefetch,
                "row_parallel": self.row_parallel, "route": self.route}

    @classmethod
    def from_dict(cls, d: dict) -> "ConvPlan":
        return cls(**{k: d[k] for k in cls.__dataclass_fields__ if k in d})


DEFAULT_PLAN = ConvPlan()


def plan_knobs(plan: "ConvPlan | None" = None, *, batch_block=UNSET,
               k_block=UNSET, c_block=UNSET, pool_row_block=UNSET,
               weight_prefetch=UNSET, row_parallel=UNSET) -> "ConvPlan":
    """The effective launch knobs for one dispatch: explicit kwarg beats
    plan beats built-in default.  ``UNSET`` marks "not passed" so an
    explicit ``c_block=None`` (force auto-sizing) still overrides a tuned
    plan's block choice."""
    base = plan if plan is not None else DEFAULT_PLAN
    return replace(
        base,
        batch_block=base.batch_block if batch_block is UNSET else batch_block,
        k_block=base.k_block if k_block is UNSET else k_block,
        c_block=base.c_block if c_block is UNSET else c_block,
        pool_row_block=(base.pool_row_block if pool_row_block is UNSET
                        else pool_row_block),
        weight_prefetch=(base.weight_prefetch if weight_prefetch is UNSET
                         else weight_prefetch),
        row_parallel=(base.row_parallel if row_parallel is UNSET
                      else row_parallel))

# resolved datapath -> (conv2d_hbm_bytes route, uses winograd transform):
# the one place benchmarks/tests translate a datapath into model terms
MODEL_ROUTES = {
    "pallas-winograd": ("pallas", True),
    "pallas-direct": ("pallas", False),
    "winograd": ("winograd", True),
    "direct": ("direct", False),
}


def conv_out_hw(extent: int, kernel: int, stride: int, padding) -> int:
    """Conv output extent (lax SAME/VALID semantics, or explicit
    ``(lo, hi)`` pads on each spatial side) — the one formula every
    spec/guard/model shares."""
    return _direct_k.pads(extent, kernel, stride, padding)[0]


def conv_pads(extent: int, kernel: int, stride: int, padding) -> tuple:
    """``(lo, hi)`` zero pads the conv reads on each side of a spatial axis
    of ``extent``: lax's split for SAME (the extra pad goes last), none for
    VALID, explicit pads as given."""
    return _direct_k.pads(extent, kernel, stride, padding)[1:]


@dataclass(frozen=True)
class ConvSpec:
    """Declarative description of one 2D conv *layer* (NHWC / HWIO).

    Beyond the conv itself, the spec owns the whole layer epilogue: bias,
    ReLU, cross-channel LRN, and spatial max-pool, in that order (the
    Krizhevsky layer graph).  Flagged stages are fused into the conv call.
    """
    kernel: int
    stride: int = 1
    padding: str | tuple = "SAME"   # "SAME" | "VALID" | (lo, hi) pads
    groups: int = 1
    fuse_bias: bool = True          # apply bias inside the conv call
    relu: bool = False              # fused ReLU epilogue
    fuse_lrn: bool = False          # fused cross-channel LRN epilogue
    lrn: LrnParams = LrnParams()    # LRN constants (used when fuse_lrn)
    fuse_pool: bool = False         # fused VALID max-pool epilogue
    pool_window: int = 3
    pool_stride: int = 2
    route: str = "auto"             # "auto" | "direct" | "winograd" | "pallas"
    winograd_m: int = 4             # F(m, 3) output tile size

    def __post_init__(self):
        assert self.route in ROUTES, self.route
        assert (self.padding in ("SAME", "VALID")
                or (isinstance(self.padding, tuple)
                    and len(self.padding) == 2
                    and min(self.padding) >= 0)), self.padding
        assert self.pool_window >= 1 and self.pool_stride >= 1

    def with_route(self, route: str) -> "ConvSpec":
        return replace(self, route=route)

    @property
    def winograd_eligible(self) -> bool:
        """3x3 stride 1 with lax SAME/VALID padding: the Winograd kernels
        take no explicit pads (a stride-1 3x3 SAME conv pads (1, 1))."""
        return (self.stride == 1 and self.kernel == 3
                and isinstance(self.padding, str))

    def out_hw(self, h: int) -> int:
        """Layer output extent for input extent ``h`` (conv then pool)."""
        h = conv_out_hw(h, self.kernel, self.stride, self.padding)
        if self.fuse_pool:
            h = pooled_hw(h, self.pool_window, self.pool_stride)
        return h


def resolve_route(spec: ConvSpec) -> str:
    """Final route after eligibility fallback (never returns "auto").

    ``pallas`` is always honored — the strided direct kernel serves every
    geometry the Winograd kernel cannot.  Only the pure-jnp ``winograd``
    route (stride-1 3x3 math, no direct twin) still falls back to
    ``direct``.
    """
    if spec.route == "auto":
        return "winograd" if spec.winograd_eligible else "direct"
    if spec.route == "winograd" and not spec.winograd_eligible:
        return "direct"
    return spec.route


def resolve_kernel(spec: ConvSpec, in_hw=None) -> str:
    """The fully resolved datapath this spec will execute — what serving
    logs report per layer (``--route pallas`` shows ``pallas-direct`` for
    conv1/conv2 instead of silently degrading to lax).

    Pass ``in_hw`` (an int extent or an (h, w) pair) to also resolve the
    one shape-dependent fallback exactly as ``dispatch_conv`` will: a
    fused pool window larger than the conv output has no VALID pooled
    region for a Pallas row block to own, so the lax path runs (and emits
    the empty pooled map).  Without ``in_hw`` that case reports the Pallas
    kernel the spec would use on a large-enough input.
    """
    route = resolve_route(spec)
    if route != "pallas":
        return route
    if in_hw is not None and spec.fuse_pool:
        hw = (in_hw, in_hw) if isinstance(in_hw, int) else in_hw
        if min(conv_out_hw(e, spec.kernel, spec.stride, spec.padding)
               for e in hw) < spec.pool_window:
            return "direct"
    return "pallas-winograd" if spec.winograd_eligible else "pallas-direct"


@dataclass(frozen=True)
class SlabFingerprint:
    """Pack-time identity of one staged weight slab: shape, dtype, a crc32
    of the packed bytes, and the pack *context* (the spec/fusion/knob
    string the slab was built under).  Computed once when the slab is
    packed; :meth:`matches` re-derives all four from the live array, so a
    corrupted slab (crc), a stale one (context — e.g. the layer was
    repacked under different fusion flags), or a mis-shaped one never
    reaches a kernel when the staging path verifies before dispatch.
    """
    shape: tuple
    dtype: str
    crc32: int
    context: str | None = None

    def matches(self, pw, *, expect=None) -> bool:
        """Verify a packed slab (or raw array) against this fingerprint;
        ``expect`` additionally pins the pack context the caller wants."""
        if expect is not None and self.context != expect:
            return False
        data = getattr(pw, "data", pw)
        if data is None or isinstance(data, jax.core.Tracer):
            return data is None     # a tracer can't be checked host-side
        host = np.asarray(data)
        return (tuple(host.shape) == tuple(self.shape)
                and str(host.dtype) == self.dtype
                and zlib.crc32(host.tobytes()) == self.crc32)


def slab_fingerprint(data, context: str | None = None):
    """Fingerprint one packed array (None/tracer -> no fingerprint; crc32
    forces a host transfer, so callers opt in at pack time only)."""
    if data is None or isinstance(data, jax.core.Tracer):
        return None
    host = np.asarray(data)
    return SlabFingerprint(shape=tuple(host.shape), dtype=str(host.dtype),
                           crc32=zlib.crc32(host.tobytes()), context=context)


def verify_packed(pw, *, expect: str | None = None) -> bool:
    """True iff ``pw`` (a :class:`PackedConvWeights` or anything duck-typed
    like one) carries an intact slab.  Values without a fingerprint have
    nothing to verify against and pass."""
    fp = getattr(pw, "fingerprint", None)
    return fp is None or fp.matches(pw, expect=expect)


@dataclass(frozen=True)
class PackedConvWeights:
    """A staged weight slab: the resolved datapath it was packed for plus
    the packed array (tile-packed DMA slab on the Pallas kernels, the
    BFP-requantized raw filters elsewhere, or None when the route has no
    packed form).

    Registered as a pytree (``data`` is the sole child; ``kernel``/``bfp``
    ride as static aux data) so a slab dict can cross a ``jax.jit``
    boundary as an *argument* — the serving engines hoist their pack-once
    slabs out of the compiled forward this way instead of re-packing
    in-trace every call (ROADMAP's donated-buffer serving refactor).

    ``fingerprint`` (a :class:`SlabFingerprint`, or None) is host-side
    integrity metadata, deliberately EXCLUDED from the pytree — it must
    never change a jit cache key, and tree ops (device_put, tree_map)
    drop it; re-attach with ``dataclasses.replace`` after moving a slab.
    """
    kernel: str                     # resolved datapath (KERNELS member)
    data: object                    # jnp array or None
    bfp: bool = False
    fingerprint: object = None      # SlabFingerprint | None (not a pytree leaf)


jax.tree_util.register_pytree_node(
    PackedConvWeights,
    lambda p: ((p.data,), (p.kernel, p.bfp)),
    lambda aux, ch: PackedConvWeights(kernel=aux[0], data=ch[0], bfp=aux[1]))


def _spec_fusion(spec: ConvSpec):
    """(lrn, pool) as the kernels see them when the bias is fused."""
    lrn_p = spec.lrn if spec.fuse_lrn else None
    pool = (spec.pool_window, spec.pool_stride) if spec.fuse_pool else None
    return lrn_p, pool


def _pallas_weight_plan(spec: ConvSpec, kernel: str, in_shape, w_shape, *,
                        lrn, pool, knobs: ConvPlan, abft: bool = False):
    """The weight-blocking plan the resolved Pallas kernel will use for
    this (spec, input shape, fusion args, launch knobs) — the one source
    of truth for slab shapes.  ``lrn``/``pool`` are the values the kernel
    call actually receives (a deferred bias strips them even when the spec
    fuses).  ``abft`` arms the checksum row, so slab shapes grow one Cb
    row per tile."""
    if kernel == "pallas-winograd":
        return _winograd_k.plan(in_shape, w_shape, m=spec.winograd_m,
                                padding=spec.padding, groups=spec.groups,
                                lrn=lrn, pool=pool, c_block=knobs.c_block,
                                pool_row_block=knobs.pool_row_block,
                                k_block=knobs.k_block,
                                batch_block=knobs.batch_block,
                                checksum=abft)
    return _direct_k.plan(in_shape, w_shape, stride=spec.stride,
                          padding=spec.padding, pool=pool,
                          groups=spec.groups, c_block=knobs.c_block,
                          pool_row_block=knobs.pool_row_block,
                          k_block=knobs.k_block,
                          batch_block=knobs.batch_block,
                          checksum=abft)


def _pack_for_plan(kernel: str, w, p, bfp_pack: bool):
    """Pack (and optionally §3.6-quantize) the slab for an already-derived
    plan — shared by the ahead-of-time staging path and the in-dispatch
    repack fallback, so quantization semantics can never diverge."""
    pack = (_winograd_k.pack_weights if kernel == "pallas-winograd"
            else _direct_k.pack_weights)
    tiles = pack(w, p)
    if bfp_pack:
        # per-tile shared exponents along the Cb contraction axis.  An
        # ABFT checksum row must cover the *final* slab bits, so strip it
        # before quantizing (the quantization blocks then still tile Cb
        # exactly) and recompute it over the requantized rows.
        if p.checksum:
            tiles = tiles[..., :-1, :]
        tiles = bfp.quantize_dequantize(
            tiles, block=math.gcd(p.weights.Cb, 32), axis=-2)
        if p.checksum:
            tiles = _dma.append_checksum_row(tiles)
    return tiles


def pack_context(spec: ConvSpec, kernel: str, *, bfp_pack: bool,
                 abft: bool, knobs: ConvPlan) -> str:
    """Canonical pack-context string — everything that changes the bytes a
    slab holds.  Stored in the fingerprint so a cache hit can detect a
    slab packed under *different* fusion flags or knobs (the silent
    stale-slab reuse the WeightStager verify path closes)."""
    return (f"{kernel}:k{spec.kernel}s{spec.stride}g{spec.groups}"
            f":{spec.padding}:relu{int(spec.relu)}"
            f":lrn{int(spec.fuse_lrn)}:pool{int(spec.fuse_pool)}"
            f"w{spec.pool_window}s{spec.pool_stride}"
            f":bfp{int(bfp_pack)}:abft{int(abft)}"
            f":kb{knobs.k_block}:bb{knobs.batch_block}")


def expected_pack_context(spec: ConvSpec, in_shape, *, bfp_pack: bool = False,
                          abft: bool = False, plan: ConvPlan | None = None,
                          k_block=UNSET, batch_block=UNSET) -> str:
    """The :func:`pack_context` string :func:`pack_conv_weights` would stamp
    for these arguments — resolved the same way (plan route override, then
    shape-aware kernel resolution), so staging-path callers can assert a
    cached slab was packed under the fusion flags and knobs they are about
    to dispatch with (``WeightStager.stage(expect=...)``)."""
    knobs = plan_knobs(plan, k_block=k_block, batch_block=batch_block)
    if plan is not None and plan.route is not None:
        spec = spec.with_route(plan.route)
    kernel = resolve_kernel(spec, in_hw=(in_shape[1], in_shape[2]))
    return pack_context(spec, kernel, bfp_pack=bfp_pack, abft=abft,
                        knobs=knobs)


def pack_conv_weights(spec: ConvSpec, in_shape, w, *, bfp_pack: bool = False,
                      abft: bool = False, fingerprint: bool = False,
                      plan: ConvPlan | None = None, k_block=UNSET,
                      batch_block=UNSET) -> PackedConvWeights:
    """Build the weight slab for one conv layer ahead of its input.

    A pure function of the layer spec, the input *shape* (B, H, W, C), and
    the raw filters — everything the §3.5 cross-layer prefetch needs to
    stage layer N+1's slab while layer N computes.  On the Pallas datapaths
    this is the full packing the kernel would otherwise do in-trace:
    Winograd filter transform (G w G^T), group/channel blocking, and the
    manual-DMA tile layout.  With ``bfp_pack`` the slab is additionally
    quantized §3.6-style (shared-exponent int8 blocks along the
    contraction dim, ``fc_bfp``'s scheme applied to the filter stream —
    the DLA's filter cache holds *transformed* filters, so quantization
    happens post-transform) and dequantized back to the compute dtype, so
    the staged values are exactly what a 1-byte weight stream would carry.

    Non-Pallas routes have no tile slab; they still get a BFP
    requantization (``data`` replaces ``w``).  Quantization follows the
    datapath's *stored filter format* — Winograd-transformed tiles on the
    Pallas kernels (as in the DLA's cache), raw filters elsewhere — so a
    ``conv_bfp`` model's routes agree only within the shared-exponent
    int8 error, not bit-wise across datapaths.

    ``plan`` is an optional tuned :class:`ConvPlan` — the slab is blocked
    for its knobs, so staging and dispatch agree when both receive the
    same plan.  Explicit ``k_block``/``batch_block`` kwargs override it.

    SDC defense: ``abft=True`` packs the slab with the per-tile ABFT
    checksum row the kernels verify in-stream (pass the same flag to
    :func:`dispatch_conv`); ``fingerprint=True`` attaches a pack-time
    :class:`SlabFingerprint` (shape/dtype/crc32/pack-context) for the
    staging-path integrity checks.  Fingerprinting forces the packed bytes
    to the host (crc32), so it is opt-in — it would otherwise serialize
    the async cross-layer staging pipeline.
    """
    knobs = plan_knobs(plan, k_block=k_block, batch_block=batch_block)
    if plan is not None and plan.route is not None:
        spec = spec.with_route(plan.route)
    kernel = resolve_kernel(spec, in_hw=(in_shape[1], in_shape[2]))
    ctx = pack_context(spec, kernel, bfp_pack=bfp_pack, abft=abft,
                       knobs=knobs)
    if kernel.startswith("pallas"):
        lrn_p, pool = _spec_fusion(spec)
        p = _pallas_weight_plan(spec, kernel, tuple(in_shape), w.shape,
                                lrn=lrn_p, pool=pool, knobs=knobs,
                                abft=abft)
        data = _pack_for_plan(kernel, w, p, bfp_pack)
    else:
        data = (bfp.quantize_dequantize(w, block=math.gcd(w.shape[2], 32),
                                        axis=2) if bfp_pack else None)
    return PackedConvWeights(
        kernel=kernel, data=data, bfp=bfp_pack,
        fingerprint=slab_fingerprint(data, ctx) if fingerprint else None)


def dispatch_conv(spec: ConvSpec, x, w, b=None, *, interpret=None,
                  w_packed: PackedConvWeights | None = None,
                  plan: ConvPlan | None = None, weight_prefetch=UNSET,
                  k_block=UNSET, batch_block=UNSET, c_block=UNSET,
                  pool_row_block=UNSET, row_parallel=UNSET,
                  abft: bool = False, prefetch_next=None,
                  name: str | None = None, residual=None):
    """Run one conv layer per its spec.  x (B,H,W,C), w (k,k,C//g,K), b (K,).

    Grouped convs are batched (``feature_group_count`` on the direct route,
    a group-folded kernel grid / vmap on the Winograd/Pallas routes) — never
    a Python loop over groups.  LRN always spans the *full* concatenated
    channel dimension, including across group seams (Krizhevsky conv2).

    Weight pipeline (§3.5): ``w_packed`` is a slab staged earlier by
    :func:`pack_conv_weights` — used directly when it matches the datapath
    and plan this call resolves to; on a mismatch (deferred-bias epilogue,
    different input shape/plan, route fallback) a ``bfp``-marked slab is
    *repacked* for the actual plan so §3.6 quantization is never silently
    dropped, and a plain slab is ignored (the kernel packs in-trace —
    identical values either way).  ``weight_prefetch`` selects the kernels'
    double-buffered manual-DMA filter stream (on, default) vs the same
    copies run synchronously (off; bit-equal).  ``prefetch_next`` is a
    zero-arg callable invoked right after the conv is issued — JAX
    dispatch is async, so work it enqueues (packing layer N+1's slab)
    overlaps this layer's compute.

    ``plan`` is an optional tuned :class:`ConvPlan` (from the measured
    autotuner): its knobs replace the built-in launch defaults, and its
    ``route`` (when set) overrides the spec's route preference.  Explicit
    knob kwargs still win over the plan (see :func:`plan_knobs`), so call
    sites can pin single knobs on top of a tuned baseline.

    ``abft=True`` arms the ABFT weight-stream verification and the return
    becomes ``(y, verdict)`` uniformly across *all* routes: the Pallas
    kernels verify each staged checksum tile after its DMA slot swap and
    report the scalar int32 mismatch count; non-Pallas routes have no DMA
    stream to corrupt, so their verdict is the constant 0.  The ``y``
    values are bit-identical to the unarmed call (the GEMMs consume the
    slab minus its checksum row).

    ``name`` is the layer's key (``conv3``): a Pallas kernel it launches is
    named ``<name>_<kernel>`` (``conv3_winograd``, ``conv1_direct``), the
    name its device op carries in a profile.

    ``residual`` (B, Ho, Wo, K), the shortcut of a residual block, is
    added after the bias and before the ReLU: conv -> +b -> +residual ->
    relu.  The direct kernel adds it in its epilogue, as a second VMEM
    operand, and is then named ``<name>_direct_add``; every other route
    adds it after the conv.  A layer with a residual has no LRN or pool.
    """
    assert w.shape[0] == w.shape[1] == spec.kernel, (w.shape, spec.kernel)
    assert residual is None or not (spec.fuse_lrn or spec.fuse_pool), (
        "a residual add ends its layer: no LRN or pool after it")
    knobs = plan_knobs(plan, batch_block=batch_block, k_block=k_block,
                       c_block=c_block, pool_row_block=pool_row_block,
                       weight_prefetch=weight_prefetch,
                       row_parallel=row_parallel)
    if plan is not None and plan.route is not None:
        spec = spec.with_route(plan.route)
    # Unfused bias is an epilogue *between* conv and ReLU
    # (conv -> +b -> relu -> lrn -> pool), so every later stage must be
    # deferred along with it.
    defer_bias = b is not None and not spec.fuse_bias
    bias = b if spec.fuse_bias else None
    kernel = resolve_kernel(spec, in_hw=(x.shape[1], x.shape[2]))
    # the direct kernel adds a residual in its epilogue; elsewhere it is
    # added after the conv, so the ReLU moves after it too
    fuse_res = (residual is not None and kernel == "pallas-direct"
                and not defer_bias)
    late_res = residual is not None and not fuse_res
    relu = spec.relu and not defer_bias and not late_res
    lrn_p = spec.lrn if spec.fuse_lrn and not defer_bias else None
    pool = ((spec.pool_window, spec.pool_stride)
            if spec.fuse_pool and not defer_bias else None)
    kname = (f"{name}_{kernel.removeprefix('pallas-')}"
             + ("_add" if fuse_res else "")
             if name and kernel.startswith("pallas") else None)

    slab = None
    if w_packed is not None and kernel.startswith("pallas"):
        p = _pallas_weight_plan(spec, kernel, x.shape, w.shape,
                                lrn=lrn_p, pool=pool, knobs=knobs,
                                abft=abft)
        want = (p.weights.n_tiles, *p.weights.tile_shape)
        if (w_packed.kernel == kernel and w_packed.data is not None
                and w_packed.data.shape == want):
            slab = w_packed.data
        elif w_packed.bfp:          # never silently drop §3.6 quantization
            slab = _pack_for_plan(kernel, w, p, True)
    elif w_packed is not None:
        if w_packed.kernel == kernel and w_packed.data is not None:
            w = w_packed.data       # BFP-requantized raw filters
        elif w_packed.bfp:          # route fell back with a stale slab
            w = bfp.quantize_dequantize(w, block=math.gcd(w.shape[2], 32),
                                        axis=2)

    if kernel == "direct":
        y = conv2d_ref(x, w, bias, stride=spec.stride, padding=spec.padding,
                       groups=spec.groups, relu=relu, lrn=lrn_p, pool=pool)
    elif kernel == "pallas-winograd":
        y = pallas_conv2d(x, w, bias, slab, m=spec.winograd_m,
                          padding=spec.padding, relu=relu, groups=spec.groups,
                          lrn=lrn_p, pool=pool, c_block=knobs.c_block,
                          pool_row_block=knobs.pool_row_block,
                          k_block=knobs.k_block,
                          batch_block=knobs.batch_block,
                          weight_prefetch=knobs.weight_prefetch,
                          row_parallel=knobs.row_parallel,
                          checksum=abft, pallas=True, interpret=interpret,
                          name=kname)
    elif kernel == "pallas-direct":
        y = pallas_conv2d_direct(x, w, bias, slab, stride=spec.stride,
                                 padding=spec.padding, relu=relu,
                                 groups=spec.groups, lrn=lrn_p, pool=pool,
                                 c_block=knobs.c_block,
                                 pool_row_block=knobs.pool_row_block,
                                 k_block=knobs.k_block,
                                 batch_block=knobs.batch_block,
                                 weight_prefetch=knobs.weight_prefetch,
                                 row_parallel=knobs.row_parallel,
                                 checksum=abft, pallas=True,
                                 interpret=interpret, name=kname,
                                 residual=residual if fuse_res else None)
    else:  # winograd (pure-jnp, differentiable)
        y = conv2d_winograd(x, w, bias, m=spec.winograd_m,
                            padding=spec.padding, relu=relu,
                            groups=spec.groups, lrn=lrn_p, pool=pool)
    verdict = None
    if abft:
        if kernel.startswith("pallas"):
            y, verdict = y
        else:
            verdict = jnp.zeros((), jnp.int32)
    if prefetch_next is not None:
        prefetch_next()             # stage layer N+1 behind this dispatch
    if defer_bias:
        y = y + b.astype(y.dtype)
    if late_res:
        y = y + residual.astype(y.dtype)
    if (defer_bias or late_res) and spec.relu:
        y = jnp.maximum(y, 0)
    if defer_bias:
        y = apply_epilogue(y,
                           spec.lrn if spec.fuse_lrn else None,
                           (spec.pool_window, spec.pool_stride)
                           if spec.fuse_pool else None)
    return (y, verdict) if abft else y
