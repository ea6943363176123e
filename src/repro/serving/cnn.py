"""Batched image-inference serving engine (paper §3.5 + §3.7, serving form).

The paper's headline number — 1020 img/s AlexNet on Arria 10 — is a *serving*
result: images are admitted, batched through the conv pipeline, and the FC
layers amortize one weight stream over S_batch images.  :class:`CnnEngine`
reproduces that request-to-prediction path in software on top of the shared
:class:`SlotScheduler` core:

* **Occupancy buckets** — each admitted group is padded to the next bucket
  (<= ``max_batch``), so ``jax.jit`` compiles a bounded set of batch
  shapes.  The ladder starts at §3.7's powers of two; under an SLO
  (``slo_ms`` + ``dynamic_buckets``) a :class:`DynamicBucketPolicy` may
  insert up to ``max_extra_buckets`` sizes at the traffic's dominant group
  size, trimming padding waste while keeping recompiles bounded.  Padded
  rows are zeros and are sliced off before retirement.
* **Admission control** — with ``slo_ms`` + ``admission`` an
  :class:`AdmissionController` sheds requests (``try_submit`` -> False,
  ``req.shed`` set, counted in ``images_shed``) whose estimated queue wait
  already busts the SLO — or the request's own ``deadline_ms``, whichever
  is tighter — protecting the goodput of requests that can still make
  their deadline.
* **Pack-once weight staging** — the model's §3.5 weight slabs
  (``pack_serving_slabs``: tile-packed, plan-blocked, optionally
  BFP-quantized) are packed exactly once per bucket shape on the host and
  passed to the compiled forward as *jit arguments* (the
  ``PackedConvWeights`` pytree), so the serving graph consumes staged
  slabs instead of re-packing filters in-trace every call.
* **Double-buffered staging** — host->device image copies are dispatched
  asynchronously up to ``staging_depth`` groups ahead; :meth:`step`
  launches group N before it stages group N+1, so the host copy and the
  H2D transfer of group N+1 overlap the forward pass of group N — the
  software analogue of the §3.5 stream buffers (``core/streambuf.py`` is
  the training-input twin of the same idea).  The slot pool is sized
  ``max_batch * staging_depth`` so a full bucket can stage while another
  computes.
* **Data parallelism** — with ``data_parallel=True`` the parameters are
  replicated over a 1-axis ``"data"`` mesh and each bucket's batch axis is
  sharded across devices (``parallel/sharding.py``).  The forward runs under
  ``shard_map``, so each device runs the whole model — Pallas kernels
  included, which XLA cannot partition — on its own slice, with slabs
  packed for that slice; buckets indivisible by the device count run
  replicated on every device.
* **Ahead-of-time bucket compiles** — each bucket's forward is lowered and
  compiled before its first launch (:meth:`CnnEngine.precompile` does the
  whole ladder up front), outside the fault handling: a forward that does
  not lower or compile for this device raises out of the engine instead of
  being retried and degraded to another route.

Fault tolerance (the chaos layer — ``serving/faults.py`` +
``serving/health.py``):

* **Named fault points** — an armed :class:`FaultInjector` is consulted at
  ``stage.corrupt`` (host staging buffer), ``launch.transient`` /
  ``launch.crash`` (forward dispatch), and ``retire.nonfinite`` /
  ``retire.latency`` (retirement); with no injector the hooks are a single
  ``is not None`` check, and an armed-but-idle injector never touches the
  data path (bit-identical serving — the CI chaos gate).
* **Deadlines + bounded retry** — ``ImageRequest.deadline_ms`` /
  ``retries``: transient launch failures and non-finite logits re-queue
  the affected requests at the queue *front* with exponential backoff
  (``retry_backoff_ms * 2**(attempt-1)``) instead of crashing the engine;
  a request past its deadline or retry budget retires as **expired**
  (``req.expired`` + ``expire_reason``, counted in ``images_expired``,
  never silently dropped).  The accounting invariant is
  ``submitted == completed + shed + expired`` once drained.
* **Health monitor + circuit breaker** — retired logits pass a sampled
  finiteness screen (``screen_sample`` rows); consecutive datapath
  failures walk healthy -> degraded -> quarantined
  (:class:`HealthMonitor`), a quarantined engine stops launching (and
  ``try_submit`` sheds) until a half-open probe succeeds after
  ``cooldown_ms``.  A hard crash quarantines immediately.
* **Route degradation ladder** — ``degrade_threshold`` repeated datapath
  failures on one bucket flip *that bucket's* compiled forward onto the
  direct route (``use_winograd=False, use_pallas=False`` — the reference
  datapath every Pallas kernel is bit-checked against), recorded as a
  degradation event rather than an outage; other buckets keep the fast
  route.
* **Silent-data-corruption defense** — with the model's ``sdc_abft`` the
  compiled forward returns ``(logits, sdc)``: the kernels verify an ABFT
  checksum row on every staged filter tile as it streams through the
  §3.5 DMA pipe, and a positive verdict at retirement means some weight
  bits changed between pack and consumption — the batch is *never
  served*; the engine repacks the bucket's slabs from the pristine
  params and retries the group (counted in ``sdc_detections``, fed to
  the health monitor / degradation ladder like any datapath failure).
  ``verify_slabs`` adds a host-side pre-dispatch fingerprint check
  (shape/dtype/crc32/pack-context) on the staged slabs — the layer that
  catches corruption *and* stale-slab reuse before a forward is burned —
  and ``screen_abs_max`` arms a magnitude bound on the retirement screen
  for finite-but-implausible logits the isfinite screen cannot see.
  Injected via the ``slab.bitflip`` / ``slab.stale`` /
  ``retire.plausible`` fault points.

Injected and real launch/device errors never escape :meth:`step`: they
are converted into the retry/health machinery above.  Only a bucket whose
forward fails to lower or compile raises.

Profiler spans (``jax.profiler.TraceAnnotation``, on the profiler's clock
with the device ops; constant names, nested as listed): ``cnn.step`` is
one :meth:`CnnEngine.step`; inside it ``cnn.stage`` (admission and the
host buffer) holds one ``cnn.put`` per group (the H2D ``device_put``,
with ``batch=<n>`` and each request's queue wait in ``queue_wait_us``),
then ``cnn.launch`` (``batch``, ``bucket``: executable lookup and the
forward call), ``cnn.fetch`` (``batch``: the blocking fetch of the
logits and the ABFT verdict) and ``cnn.retire`` (screen, bookkeeping).
``cnn.compile`` (``bucket``) wraps each bucket's lower-and-compile.  With
no profiler running a span costs about a microsecond.

Request lifecycle: submit() -> queued -> admitted (slots held for one
bucketed forward) -> staged (H2D in flight) -> computing -> finished
(logits + argmax label on the request), with shed / expired as the
reported non-success terminals and retry loops back to queued.  Metrics
mirror Tables 5-6: img/s, average occupancy, per-bucket batch counts,
p50/p90/p99 request latency — plus the fleet-serving companions: shed /
expired / retried counts, within-SLO completions, goodput img/s, health
state, and the accounting block.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P, SingleDeviceSharding

from ..models import model_for
from ..parallel.sharding import (batch_sharding, data_parallel_mesh,
                                 replicated_sharding)
from .clock import MONOTONIC, Clock
from .faults import EngineCrash, FaultInjector, TransientLaunchError
from .health import QUARANTINED, HealthMonitor
from .policy import AdmissionController, DynamicBucketPolicy, bucket_sizes
from .scheduler import DrainTimeout, LatencyTracker, SlotScheduler

__all__ = ["CnnEngine", "CnnServeConfig", "ImageRequest", "bucket_sizes"]


def _copy_image(dst: np.ndarray, img: np.ndarray):
    """``dst[...] = img`` for one ``(H, W, C)`` image into the C-ordered
    staging buffer.  numpy runs its inner loop along the destination's
    fastest axis, C, so an image whose channels are planes (transposed
    from CHW, or fetched from a device in its layout) would cost one loop
    per pixel; it is copied a plane at a time instead, W innermost."""
    img = np.asarray(img)
    if img.strides[-1] == img.itemsize:
        dst[...] = img
    else:
        for ch in range(img.shape[-1]):
            dst[..., ch] = img[..., ch]


@dataclass
class CnnServeConfig:
    max_batch: int = 8          # largest serve bucket (paper's S_batch knob)
    staging_depth: int = 2      # groups staged ahead of compute (§3.5 buffer)
    data_parallel: bool = False  # shard bucket batch axis over jax.devices()
    # -- SLO control plane (serving/policy.py) --------------------------
    slo_ms: Optional[float] = None  # p99 latency SLO; None = no SLO policy
    dynamic_buckets: bool = False   # SLO-driven bucket-ladder resizing
    admission: bool = False         # SLO-driven load shedding (try_submit)
    max_extra_buckets: int = 2      # bound on inserted bucket shapes
    policy_window: int = 64         # sliding window the policy reacts to
    admission_slack: float = 1.0    # shed when est. wait > slo_ms * slack
    latency_window: int = 4096      # LatencyTracker ring size (bounded)
    # -- fault tolerance (serving/faults.py + serving/health.py) --------
    retry_backoff_ms: float = 1.0   # exponential retry backoff base
    screen_sample: int = 8          # retired rows finiteness-screened (0=off)
    fail_threshold: int = 3         # consecutive failures -> degraded
    quarantine_threshold: int = 6   # consecutive failures -> quarantined
    cooldown_ms: float = 250.0      # circuit-breaker half-open cooldown
    degrade_threshold: int = 3      # per-bucket failures -> direct-route flip
    # -- SDC defense (ABFT verdicts ride the model's sdc_abft flag) -----
    verify_slabs: bool = False      # pre-dispatch slab fingerprint check
    screen_abs_max: Optional[float] = None  # |logit| bound on the screen


@dataclass
class ImageRequest:
    image: np.ndarray           # (H, W, C) host-side float image
    uid: int = field(default_factory=itertools.count().__next__)
    # -- fault-tolerance contract --------------------------------------
    deadline_ms: Optional[float] = None  # relative to submit; None = none
    retries: int = 2            # transient-failure re-launch budget
    attempts: int = 0           # failed launch/screen attempts consumed
    # outputs
    logits: Optional[np.ndarray] = None   # (num_classes,) on completion
    label: Optional[int] = None           # argmax of logits
    done: bool = False
    shed: bool = False          # rejected by admission control (never served)
    expired: bool = False       # deadline or retry budget exhausted
    expire_reason: Optional[str] = None   # "deadline" | "retries"
    t_submit: float = 0.0
    t_admit: float = 0.0        # admitted into the group that serves it
    t_done: float = 0.0
    # serving provenance (set at retirement): the padded bucket shape this
    # request was served at, its row in that batch, and the uids of every
    # request in the group (row order).  A failover verifier rebuilds the
    # exact staged buffer from these and bit-checks against the jitted
    # direct forward at the same padded shape.
    served_bucket: Optional[int] = None
    served_row: Optional[int] = None
    served_group: Optional[Tuple[int, ...]] = None


@dataclass
class _Group:
    """One admitted batch moving through the stage->compute->retire pipe."""
    slots: List[int]
    reqs: List[ImageRequest]
    bucket: int
    images: object              # device array (bucket, H, W, C), H2D async
    logits: object = None       # device array once compute is dispatched
    sdc: object = None          # device scalar ABFT verdict (sdc_abft only)
    t_launch: float = 0.0       # forward dispatch time (service-time EWMA)
    seq: int = 0                # batch number, on its put/launch/fetch spans


class CnnEngine:
    def __init__(self, cfg, scfg: CnnServeConfig, *, params=None,
                 seed: int = 0, faults: Optional[FaultInjector] = None,
                 clock: Optional[Clock] = None):
        self.cfg, self.scfg = cfg, scfg
        # injectable time source: deadlines, retry backoff, cooldowns, and
        # the injected latency spike all read this clock, so chaos replays
        # and timing tests run deterministic + sleep-free on VirtualClock
        self.clock = clock or MONOTONIC
        self.mod = model_for(cfg)
        if params is None:
            params = self.mod.init(jax.random.PRNGKey(seed), cfg)
        self._buckets = bucket_sizes(scfg.max_batch)
        self.sched = SlotScheduler(scfg.max_batch * scfg.staging_depth)
        self.mesh = data_parallel_mesh() if scfg.data_parallel else None
        if self.mesh is not None:
            params = jax.device_put(params, replicated_sharding(self.mesh))
        self.params = params
        # staging buffers carry the model's configured dtype — a non-fp32
        # model must not be silently fed fp32 (wrong input dtype + 2x the
        # H2D bytes the §3.5 stream buffer is sized for)
        self._buf_dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))

        # SLO control plane: bucket resizing + load shedding (policy.py)
        self.policy = (DynamicBucketPolicy(
            scfg.max_batch, scfg.slo_ms, max_extra=scfg.max_extra_buckets,
            window=scfg.policy_window)
            if scfg.slo_ms and scfg.dynamic_buckets else None)
        self.admission = (AdmissionController(
            scfg.slo_ms, slack=scfg.admission_slack)
            if scfg.slo_ms and scfg.admission else None)

        # fault-tolerance plane: seeded chaos hooks (None = zero-overhead
        # pass-through) + the health state machine / circuit breaker
        self.faults = faults
        self.health = HealthMonitor(
            fail_threshold=scfg.fail_threshold,
            quarantine_threshold=scfg.quarantine_threshold,
            cooldown_ms=scfg.cooldown_ms,
            clock=self.clock)

        # route degradation ladder: the direct-route twin config this
        # engine falls back to per bucket after repeated datapath failures
        # (None when the model has no route knobs or already runs direct)
        uw = getattr(cfg, "use_winograd", None)
        if uw is None:
            self._primary_route, self._cfg_direct = "n/a", None
        else:
            self._primary_route = (
                "pallas" if getattr(cfg, "use_pallas", False)
                else ("winograd" if uw else "direct"))
            self._cfg_direct = (
                dataclasses.replace(cfg, use_winograd=False,
                                    use_pallas=False)
                if self._primary_route != "direct" else None)
        self._degraded: Set[int] = set()
        self._bucket_failures: Dict[int, int] = {}
        self.degradations: List[dict] = []

        # tuned launch plans from the measured autotuner's persisted cache
        # (results/plans/) — loaded at build, keyed to this config's layer
        # geometries on the current backend; {} runs the defaults.  Plans
        # are bit-equal re-blockings, so serving outputs are unchanged.
        self.plans: Dict[str, object] = {}
        if hasattr(self.mod, "load_tuned_plans"):
            self.plans = self.mod.load_tuned_plans(cfg, scfg.max_batch)

        # pack-once serving forward: weight slabs are packed per bucket
        # shape on the host (_slabs) and enter the compiled graph as jit
        # *arguments*.  The staged image buffer is not donated: no output
        # has its shape, so XLA could not reuse it.
        mod, ccfg, plans = self.mod, cfg, self.plans
        self._hoist = hasattr(mod, "pack_serving_slabs")
        # SDC defense plane: when the model config arms sdc_abft the
        # compiled forward returns (logits, verdict) and retirement gates
        # on the verdict; verify_slabs adds the pre-dispatch fingerprint
        # check on the hoisted slabs.
        self._abft = bool(getattr(cfg, "sdc_abft", False))
        self.sdc_detections = 0
        self.slab_integrity_failures = 0
        self.screen_nonfinite = 0
        self.screen_magnitude = 0
        self._packed: Dict[int, dict] = {}
        self._packed_direct: Dict[int, dict] = {}
        # ahead-of-time compiled forwards per bucket (primary / degraded
        # route) and the seconds each compile took
        self.executables: Dict[int, object] = {}
        self._executables_direct: Dict[int, object] = {}
        self.compile_seconds: Dict[int, float] = {}
        self._forwards: Dict[tuple, object] = {}
        if self._hoist:
            self._fn = (lambda p, slabs, x: mod.apply(p, ccfg, x, plans=plans,
                                                      packed=slabs))
        else:
            self._fn = ((lambda p, x: mod.apply(p, ccfg, x, plans=plans))
                        if plans else (lambda p, x: mod.apply(p, ccfg, x)))
        self._staged: Deque[_Group] = deque()
        self._compute: Deque[_Group] = deque()
        self._batch_seq = itertools.count()
        # retry holding pen: (ready_time, [reqs]) groups waiting out their
        # exponential backoff before re-queueing at the queue front
        self._retry: List[Tuple[float, List[ImageRequest]]] = []
        self.latency = LatencyTracker(window=scfg.latency_window)
        self.images_submitted = 0
        self.images_completed = 0
        self.images_shed = 0
        self.images_expired = 0
        self.images_retried = 0
        self.images_within_slo = 0
        self.batches_run = 0
        self.batches_failed = 0
        self.bucket_counts: Dict[int, int] = {}
        self.shed_reasons: Dict[str, int] = {}
        self._t_serve = 0.0

    def arm_slo(self, slo_ms: Optional[float], *, dynamic_buckets: bool =
                False, admission: bool = False):
        """Arm (or replace) the SLO control plane on a live engine.

        Serving deployments calibrate the SLO from *measured* service
        times — which needs a warmed engine — so the control plane must be
        attachable after warmup.  Compiled buckets, packed slabs, and
        counters are all kept; only the policy objects are rebuilt.
        """
        scfg = dataclasses.replace(self.scfg, slo_ms=slo_ms,
                                   dynamic_buckets=dynamic_buckets,
                                   admission=admission)
        self.scfg = scfg
        self.policy = (DynamicBucketPolicy(
            scfg.max_batch, scfg.slo_ms, max_extra=scfg.max_extra_buckets,
            window=scfg.policy_window)
            if scfg.slo_ms and scfg.dynamic_buckets else None)
        self.admission = (AdmissionController(
            scfg.slo_ms, slack=scfg.admission_slack)
            if scfg.slo_ms and scfg.admission else None)

    def arm_faults(self, injector: Optional[FaultInjector]):
        """Attach (or detach) a fault injector on a live engine — chaos
        runs arm after jit warmup so the fault schedule's opportunity
        indices count serving launches, not compiles."""
        self.faults = injector

    # ------------------------------------------------------------------
    @property
    def buckets(self) -> Tuple[int, ...]:
        """The current bucket ladder (static, or the policy's resized
        ladder under ``dynamic_buckets``)."""
        return self.policy.buckets() if self.policy else self._buckets

    def _validate(self, req: ImageRequest):
        expect = (self.cfg.image_size, self.cfg.image_size,
                  self.cfg.in_channels)
        shape = np.shape(req.image)
        if shape != expect:
            raise ValueError(f"image shape {shape} != expected {expect} "
                             f"for {self.cfg.name}")

    def submit(self, req: ImageRequest):
        """Unconditional submit (no admission control) — validates shape
        and queues the request."""
        self._validate(req)
        req.t_submit = self.clock.now()
        self.images_submitted += 1
        self.sched.submit(req)

    def backlog_images(self) -> int:
        """Images ahead of a newcomer: queued + staged + computing +
        waiting out a retry backoff."""
        return (len(self.sched.queue)
                + sum(len(g.reqs) for g in self._staged)
                + sum(len(g.reqs) for g in self._compute)
                + self.retry_pending)

    def shed(self, req: ImageRequest, reason: str = "admission"):
        """Mark + count one shed request (reported, never dropped): the
        request still figures in ``submitted`` so the accounting invariant
        ``submitted == completed + shed + expired`` closes."""
        req.shed = True
        self.images_submitted += 1
        self.images_shed += 1
        self.shed_reasons[reason] = self.shed_reasons.get(reason, 0) + 1

    def try_submit(self, req: ImageRequest) -> bool:
        """Admission-controlled submit: returns False (and marks
        ``req.shed``) when the engine is quarantined or the SLO controller
        estimates the queue can no longer absorb the request before its
        budget (SLO or the request's own deadline); shed requests are
        counted in ``images_shed`` and never occupy a slot."""
        self._validate(req)
        if self.health.state == QUARANTINED:
            self.shed(req, "unhealthy")
            return False
        if (self.admission is not None
                and not self.admission.admit(self.backlog_images(),
                                             deadline_ms=req.deadline_ms)):
            self.shed(req, "admission")
            return False
        req.t_submit = self.clock.now()
        self.images_submitted += 1
        self.sched.submit(req)
        return True

    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding ``n`` requests.  A group larger than
        ``max_batch`` is a contract violation — admission must never build
        one — and raises instead of silently padding past the ladder
        (which would compile an undeclared shape)."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"group of {n} exceeds max_batch={self.buckets[-1]}; "
            f"admission must cap groups at the largest bucket")

    def _shards(self, bucket: int) -> int:
        """Devices one bucket's batch splits over under data parallelism
        (1: unsharded, or replicated when the bucket is indivisible)."""
        n = 1 if self.mesh is None else self.mesh.devices.size
        return n if bucket % n == 0 else 1

    def _image_sharding(self, bucket: int):
        if self.mesh is None:
            return SingleDeviceSharding(jax.devices()[0])
        if self._shards(bucket) > 1:
            return batch_sharding(self.mesh, 4)
        return replicated_sharding(self.mesh)

    def _put(self, host: np.ndarray):
        """Async H2D copy (transfer overlaps in-flight compute)."""
        return jax.device_put(host, self._image_sharding(host.shape[0]))

    def _forward(self, bucket: int, degraded: bool):
        """The jitted forward one bucket launches: the model's own under
        one device; under data parallelism a ``shard_map`` over
        ``"data"`` in which every device runs the whole forward on its
        slice of the batch (or on the whole batch, replicated, when the
        bucket is indivisible) — XLA cannot partition a Pallas call, so
        the batch must be split before the kernels see it."""
        split = self._shards(bucket) > 1
        key = (degraded, split)
        if key not in self._forwards:
            fn = self._direct_fn() if degraded else self._fn
            if self.mesh is not None:
                fn = self._shard_mapped(fn, split)
            self._forwards[key] = jax.jit(fn)
        return self._forwards[key]

    def _shard_mapped(self, fn, split: bool):
        x_spec = P("data") if split else P()
        abft = self._abft

        def body(*args):
            out = fn(*args)
            if abft and split:              # one verdict over all slices
                logits, sdc = out
                return logits, jax.lax.psum(sdc, "data")
            return out

        nargs = 3 if self._hoist else 2
        return jax.shard_map(body, mesh=self.mesh,
                             in_specs=(P(),) * (nargs - 1) + (x_spec,),
                             out_specs=(x_spec, P()) if abft else x_spec,
                             check_vma=False)

    def _executable(self, bucket: int, degraded: bool, images):
        """The compiled forward for one bucket, lowered and compiled on
        first use.  Deliberately outside the launch fault handling: a
        forward that does not lower or compile for this device is a defect
        of the datapath, not a transient fault, and raises out of the
        engine rather than being retried and degraded to another route.
        ``images`` is the staged buffer or a ``ShapeDtypeStruct`` of it."""
        cache = self._executables_direct if degraded else self.executables
        if bucket not in cache:
            args = ((self.params, self._slabs_for(bucket, degraded), images)
                    if self._hoist else (self.params, images))
            t0 = time.perf_counter()
            with TraceAnnotation("cnn.compile", bucket=bucket):
                cache[bucket] = self._forward(bucket, degraded).lower(
                    *args).compile()
            if not degraded:
                self.compile_seconds[bucket] = time.perf_counter() - t0
        return cache[bucket]

    def precompile(self) -> Dict[int, float]:
        """Lower and compile every bucket of the current ladder before any
        traffic; returns the compile seconds per bucket.  Raises what the
        compiler raises."""
        hw, c = self.cfg.image_size, self.cfg.in_channels
        for b in self.buckets:
            self._executable(b, False, jax.ShapeDtypeStruct(
                (b, hw, hw, c), self._buf_dtype,
                sharding=self._image_sharding(b)))
        return {b: self.compile_seconds[b] for b in self.buckets}

    def _slabs_for(self, bucket: int, degraded: bool):
        return self._slabs_direct(bucket) if degraded else self._slabs(bucket)

    def _slabs(self, bucket: int):
        """The hoisted pack-once weight slabs for one bucket shape (packed
        on first use, then reused as jit arguments for every forward of
        that bucket — the compiled-path twin of the eager WeightStager).
        Under data parallelism they are packed for one device's slice."""
        if bucket not in self._packed:
            kw = ({"fingerprint": True} if self.scfg.verify_slabs else {})
            packed = self.mod.pack_serving_slabs(
                self.params, self.cfg, bucket // self._shards(bucket),
                plans=self.plans, **kw)
            if self.mesh is not None:
                packed = jax.device_put(packed,
                                        replicated_sharding(self.mesh))
            self._packed[bucket] = packed
        return self._packed[bucket]

    # -- fault-tolerance internals -------------------------------------
    def _is_expired(self, req: ImageRequest, now: float) -> bool:
        return (req.deadline_ms is not None
                and now >= req.t_submit + req.deadline_ms / 1e3)

    def _retire_expired(self, req: ImageRequest, reason: str):
        """Terminal non-success retirement: reported via ``req.expired``
        and ``images_expired`` — never silently dropped."""
        req.expired = True
        req.expire_reason = reason
        self.images_expired += 1

    def _schedule_retry(self, reqs: List[ImageRequest], now: float):
        if not reqs:
            return
        attempt = min(r.attempts for r in reqs)
        delay_s = (self.scfg.retry_backoff_ms
                   * (2 ** max(attempt - 1, 0))) / 1e3
        self._retry.append((now + delay_s, reqs))
        self.images_retried += len(reqs)

    def _fail_one(self, slot: int, req: ImageRequest, now: float,
                  retry: List[ImageRequest]):
        """Disposition one request after a failed attempt: slot freed
        (no completion counted), then retry / expire by budget."""
        self.sched.release(slot)
        req.attempts += 1
        if self._is_expired(req, now):
            self._retire_expired(req, "deadline")
        elif req.attempts > req.retries:
            self._retire_expired(req, "retries")
        else:
            retry.append(req)

    def _requeue_group(self, g: _Group):
        """A whole-group launch failure: free the slots and send every
        request through the retry/expiry disposition with backoff."""
        now = self.clock.now()
        retry: List[ImageRequest] = []
        for slot, req in zip(g.slots, g.reqs):
            self._fail_one(slot, req, now, retry)
        self._schedule_retry(retry, now)

    def _pump_retries(self):
        """Move retry groups whose backoff has elapsed to the queue front
        (they keep FIFO seniority); expire any that ran out of deadline
        while waiting."""
        if not self._retry:
            return
        now = self.clock.now()
        ready = [e for e in self._retry if e[0] <= now]
        if not ready:
            return
        self._retry = [e for e in self._retry if e[0] > now]
        for _, reqs in sorted(ready, key=lambda e: e[0], reverse=True):
            live = []
            for r in reqs:
                if self._is_expired(r, now):
                    self._retire_expired(r, "deadline")
                else:
                    live.append(r)
            if live:
                self.sched.requeue(live)

    def _note_datapath_failure(self, bucket: int, kind: str):
        """Count per-bucket datapath failures toward the degradation
        ladder: ``degrade_threshold`` repeated failures flip that bucket's
        forward onto the direct route (recorded, not an outage)."""
        if self._cfg_direct is None or bucket in self._degraded:
            return
        n = self._bucket_failures.get(bucket, 0) + 1
        self._bucket_failures[bucket] = n
        if n >= self.scfg.degrade_threshold:
            self._degraded.add(bucket)
            self.degradations.append({
                "bucket": bucket, "reason": kind, "failures": n,
                "from": self._primary_route, "to": "direct"})

    def _direct_fn(self):
        """The degraded-bucket forward: same model, direct route (the
        bit-checked reference datapath), no tuned plans."""
        mod, cfg_d = self.mod, self._cfg_direct
        if self._hoist:
            return lambda p, slabs, x: mod.apply(p, cfg_d, x, packed=slabs)
        return lambda p, x: mod.apply(p, cfg_d, x)

    def _slabs_direct(self, bucket: int):
        if bucket not in self._packed_direct:
            packed = self.mod.pack_serving_slabs(
                self.params, self._cfg_direct,
                bucket // self._shards(bucket))
            if self.mesh is not None:
                packed = jax.device_put(packed,
                                        replicated_sharding(self.mesh))
            self._packed_direct[bucket] = packed
        return self._packed_direct[bucket]

    # -- SDC defense internals -----------------------------------------
    def _slab_entries(self, packed: dict) -> List[str]:
        """Names of the packed entries that are injectable/verifiable conv
        slabs (a device tile array behind a PackedConvWeights), sorted for
        deterministic payload-RNG indexing."""
        return sorted(k for k, v in packed.items()
                      if hasattr(v, "kernel")
                      and getattr(v, "data", None) is not None)

    def _inject_bitflip(self, bucket: int):
        """``slab.bitflip`` payload: flip one bit — layer, byte, and bit
        position all drawn from the point's seeded payload stream — in the
        bucket's staged slab cache.  The pristine params are untouched, so
        the repack after detection restores a clean slab."""
        packed = self._slabs(bucket)
        names = self._slab_entries(packed)
        if not names:
            return
        rng = self.faults.payload_rng("slab.bitflip")
        name = names[int(rng.integers(len(names)))]
        pw = packed[name]
        host = np.array(jax.device_get(pw.data))
        flat = host.view(np.uint8).reshape(-1)
        flat[int(rng.integers(flat.size))] ^= np.uint8(
            1 << int(rng.integers(8)))
        self._packed[bucket] = {
            **packed, name: dataclasses.replace(
                pw, data=jax.device_put(host, pw.data.sharding))}

    def _inject_stale(self, bucket: int):
        """``slab.stale`` payload: one layer's cache entry starts serving a
        *different* layer's slab data (its pack-time fingerprint stays, so
        only the fingerprint check can tell) — the silent stale-reuse bug
        class the ``verify_slabs`` path exists to catch."""
        packed = self._slabs(bucket)
        names = self._slab_entries(packed)
        if len(names) < 2:
            return
        rng = self.faults.payload_rng("slab.stale")
        i = int(rng.integers(len(names)))
        victim, donor = names[i], names[(i + 1) % len(names)]
        self._packed[bucket] = {
            **packed, victim: dataclasses.replace(
                packed[victim], data=packed[donor].data)}

    def _slabs_intact(self, bucket: int, degraded: bool) -> bool:
        """Pre-dispatch fingerprint verification of the bucket's staged
        slabs (shape/dtype/crc32 against pack time).  Unfingerprinted
        entries pass — the check is opt-in per slab."""
        cache = self._packed_direct if degraded else self._packed
        packed = cache.get(bucket)
        if packed is None:
            return True
        from ..nn.conv import verify_packed
        return all(verify_packed(v) for v in packed.values()
                   if hasattr(v, "kernel"))

    def _fail_batch(self, g: _Group, kind: str, *, repack: bool = False):
        """Common datapath-failure disposition: count, feed health and the
        degradation ladder, optionally drop the bucket's staged slabs (so
        the retry repacks from the pristine params), re-queue the group."""
        self.batches_failed += 1
        self.health.record_failure(kind)
        self._note_datapath_failure(g.bucket, kind)
        if repack:
            self._packed.pop(g.bucket, None)
            self._packed_direct.pop(g.bucket, None)
        self._requeue_group(g)

    def _screen(self, logits: np.ndarray) -> np.ndarray:
        """Sampled screen on retired logits: True = row may be served.
        ``screen_sample`` rows are checked (all rows when the sample covers
        the group).  Two verdicts, counted separately: a NaN/Inf row
        (``screen_nonfinite``) and — with ``screen_abs_max`` — a finite row
        whose magnitude busts the bound (``screen_magnitude``, the
        plausible-corruption class ``retire.plausible`` injects).  A
        screened-out row is never served; the request retries from its
        pristine host image instead."""
        n = len(logits)
        ok = np.ones(n, bool)
        k = self.scfg.screen_sample
        if not n or k <= 0:
            return ok
        idx = (np.arange(n) if k >= n
               else np.unique(np.linspace(0, n - 1, k).astype(int)))
        rows = logits[idx].astype(np.float32)
        finite = np.isfinite(rows).all(axis=1)
        self.screen_nonfinite += int((~finite).sum())
        ok[idx] = finite
        amax = self.scfg.screen_abs_max
        if amax is not None:
            bounded = (np.abs(np.where(np.isfinite(rows), rows, 0.0))
                       .max(axis=1) <= amax)
            self.screen_magnitude += int((finite & ~bounded).sum())
            ok[idx] &= bounded
        return ok

    def _quarantine_purge(self):
        """While the circuit is open: unstage held groups (slots freed,
        requests back to the queue front — they re-stage after recovery)
        and expire overdue queued requests so a quarantined engine still
        drains instead of hoarding work."""
        now = self.clock.now()
        while self._staged:
            g = self._staged.popleft()
            live = []
            for slot, req in zip(g.slots, g.reqs):
                self.sched.release(slot)
                if self._is_expired(req, now):
                    self._retire_expired(req, "deadline")
                else:
                    live.append(req)
            if live:
                self.sched.requeue(live)
        q = self.sched.queue
        for _ in range(len(q)):         # stable full rotation
            r = q.popleft()
            if self._is_expired(r, now):
                self._retire_expired(r, "deadline")
            else:
                q.append(r)

    # -- pipeline ------------------------------------------------------
    def _stage(self):
        """Admit queued requests into free slots and start their H2D copies.
        Requests already past their deadline at admission retire as
        expired instead of burning a forward.  Each admitted request is
        stamped ``t_admit``; the group's ``cnn.put`` span carries its batch
        number and every request's queue wait in microseconds."""
        with TraceAnnotation("cnn.stage"):
            while (self.sched.queue
                   and len(self._staged) + len(self._compute)
                   < self.scfg.staging_depth):
                group = self.sched.admit(limit=self.scfg.max_batch)
                if not group:
                    break                                   # no free slots
                now = self.clock.now()
                slots, reqs = [], []
                for s, r in group:
                    if self._is_expired(r, now):
                        self.sched.release(s)
                        self._retire_expired(r, "deadline")
                    else:
                        r.t_admit = now
                        slots.append(s)
                        reqs.append(r)
                if not reqs:
                    continue
                if self.policy is not None:
                    self.policy.observe_admit(len(reqs))
                bucket = self.bucket_for(len(reqs))
                h, w, c = reqs[0].image.shape
                buf = np.zeros((bucket, h, w, c), self._buf_dtype)
                for i, r in enumerate(reqs):
                    _copy_image(buf[i], r.image)
                if (self.faults is not None
                        and self.faults.fire("stage.corrupt")):
                    # corrupt only the staged copy — req.image stays
                    # pristine, so the retry after the finiteness screen
                    # re-stages clean
                    buf[0] = np.nan
                seq = next(self._batch_seq)
                waits = " ".join(str(round((now - r.t_submit) * 1e6))
                                 for r in reqs)
                with TraceAnnotation("cnn.put", batch=seq,
                                     queue_wait_us=waits):
                    images = self._put(buf)
                self._staged.append(_Group(slots, reqs, bucket, images,
                                           seq=seq))

    def _launch(self):
        """Dispatch the forward pass for the oldest staged group (async).
        Launch failures — injected or real — never escape: the group
        re-queues with backoff and the health monitor is fed."""
        if not self._staged:
            return
        g = self._staged.popleft()
        with TraceAnnotation("cnn.launch", batch=g.seq, bucket=g.bucket):
            self._dispatch(g)

    def _dispatch(self, g: _Group):
        degraded = g.bucket in self._degraded
        # slab chaos (hoisted primary-route path only — that is where a
        # staged slab cache exists to corrupt) + the pre-dispatch
        # fingerprint gate: a corrupted or stale slab never reaches a
        # forward; the bucket repacks from pristine params and the group
        # retries with backoff.
        if self.faults is not None and self._hoist and not degraded:
            if self.faults.fire("slab.bitflip"):
                self._inject_bitflip(g.bucket)
            if self.faults.fire("slab.stale"):
                self._inject_stale(g.bucket)
        if (self.scfg.verify_slabs and self._hoist
                and not self._slabs_intact(g.bucket, degraded)):
            self.slab_integrity_failures += 1
            self._fail_batch(g, "slab", repack=True)
            return
        forward = self._executable(g.bucket, degraded, g.images)
        g.t_launch = self.clock.now()
        try:
            if self.faults is not None:
                if self.faults.fire("launch.crash"):
                    raise EngineCrash("injected hard engine crash")
                if self.faults.fire("launch.transient"):
                    raise TransientLaunchError(
                        "injected transient launch failure "
                        "(RESOURCE_EXHAUSTED)")
            if self._hoist:
                g.logits = forward(self.params,
                                   self._slabs_for(g.bucket, degraded),
                                   g.images)
            else:
                g.logits = forward(self.params, g.images)
            if self._abft:
                g.logits, g.sdc = g.logits
        except EngineCrash as e:
            self.batches_failed += 1
            self.health.force_quarantine(f"crash: {e}")
            self._note_datapath_failure(g.bucket, "crash")
            self._requeue_group(g)
            return
        except Exception:       # transient injected or real launch error
            self.batches_failed += 1
            self.health.record_failure("launch")
            self._note_datapath_failure(g.bucket, "launch")
            self._requeue_group(g)
            return
        self._compute.append(g)

    def _finish_oldest(self):
        """Block on the oldest computed group's logits (and its ABFT
        verdict, when armed), then retire its requests."""
        if not self._compute:
            return
        g = self._compute.popleft()
        with TraceAnnotation("cnn.fetch", batch=g.seq):
            try:
                logits = np.asarray(jax.device_get(g.logits))[: len(g.reqs)]
            except Exception:       # async device error surfaces at fetch
                self._fail_batch(g, "device")
                return
            sdc = (int(np.asarray(jax.device_get(g.sdc)))
                   if self._abft and g.sdc is not None else 0)
        with TraceAnnotation("cnn.retire"):
            self._retire(g, logits, sdc)

    def _retire(self, g: _Group, logits: np.ndarray, sdc: int):
        """Retired logits pass the sampled finiteness screen; bad rows retry
        (never served), clean rows retire normally."""
        # ABFT verdict gate: a positive in-kernel checksum mismatch count
        # means the staged filter bits changed between pack and the DMA
        # stream — the whole batch is tainted and is *never served*.  The
        # bucket's slab cache is dropped (retry repacks from the pristine
        # params) and the group re-queues with backoff, so detection feeds
        # the same retry/health/degradation machinery as any datapath
        # failure.  This runs before any retire-stage chaos: the verdict
        # belongs to the forward that computed these logits.
        if sdc > 0:
            self.sdc_detections += 1
            self._fail_batch(g, "sdc", repack=True)
            return
        if self.faults is not None:
            spec = self.faults.fire("retire.latency")
            if spec is not None and spec.delay_ms:
                self.clock.sleep(spec.delay_ms / 1e3)
            if self.faults.fire("retire.nonfinite"):
                logits = np.array(logits)       # own the buffer
                logits[0] = np.nan
            spec = self.faults.fire("retire.plausible")
            if spec is not None:
                # finite, bounded-magnitude corruption — crafted to pass
                # the isfinite screen; only screen_abs_max can catch it
                logits = np.array(logits)
                rng = self.faults.payload_rng("retire.plausible")
                row = int(rng.integers(len(logits)))
                logits[row] = logits[row] + (spec.magnitude or 1e8)
        ok = self._screen(logits)
        now = self.clock.now()
        slo_s = (self.scfg.slo_ms or 0.0) / 1e3
        n_good = 0
        retry: List[ImageRequest] = []
        group_uids = tuple(r.uid for r in g.reqs)
        for i, (slot, req, row, good) in enumerate(
                zip(g.slots, g.reqs, logits, ok)):
            if not good:
                self._fail_one(slot, req, now, retry)
                continue
            req.logits = row
            req.label = int(row.argmax())
            req.done = True
            req.t_done = now
            # serving provenance: enough to rebuild the exact padded batch
            # this row came from (failover bit-parity verification)
            req.served_bucket = g.bucket
            req.served_row = i
            req.served_group = group_uids
            lat = now - req.t_submit
            self.latency.record(lat)
            if slo_s and lat <= slo_s:
                self.images_within_slo += 1
            if self.policy is not None:
                self.policy.observe_latency(lat)
            self.sched.retire(slot)
            n_good += 1
        self._schedule_retry(retry, now)
        if n_good == len(g.reqs):
            self.health.record_ok()
            self._bucket_failures[g.bucket] = 0
        else:
            self.health.record_failure("nonfinite")
            self._note_datapath_failure(g.bucket, "nonfinite")
        # service-time EWMA feeds load shedding; compiles happen before
        # t_launch, so every launch is a clean sample
        if self.admission is not None and n_good:
            self.admission.observe_batch(n_good, now - g.t_launch)
        if self.policy is not None:
            self.policy.maybe_resize()
        self.images_completed += n_good
        self.batches_run += 1
        self.bucket_counts[g.bucket] = self.bucket_counts.get(g.bucket, 0) + 1

    def step(self):
        """One tick: pump elapsed retries, launch the oldest staged group,
        stage the next (its host copy and H2D transfer overlap that
        forward; a group staged on an idle device launches at once),
        retire the oldest computed.  Under quarantine the circuit is open:
        nothing launches except the half-open probe after ``cooldown_ms``,
        and queued work drains via deadline expiry.  No Python exception
        escapes this method for launch/device failures — they feed the
        retry + health machinery instead; a bucket whose forward does not
        lower or compile raises (:meth:`_executable`)."""
        t0 = self.clock.now()
        with TraceAnnotation("cnn.step"):
            self._pump_retries()
            if self.health.state == QUARANTINED:
                self._quarantine_purge()
                if (self.sched.queue
                        and len(self._staged) + len(self._compute)
                        < self.scfg.staging_depth
                        and self.health.allow_launch()):
                    self._stage()
                    if self._staged:
                        self._launch()              # the half-open probe
                    else:
                        self.health.cancel_probe()  # nothing admissible
            else:
                self._launch()
                self._stage()
                if not self._compute:
                    self._launch()
            self._finish_oldest()
        self._t_serve += self.clock.now() - t0

    @property
    def retry_pending(self) -> int:
        return sum(len(rs) for _, rs in self._retry)

    @property
    def drained(self) -> bool:
        """No queued, staged, computing, or backoff-pending work."""
        return (self.sched.idle and not self._staged and not self._compute
                and not self._retry)

    def drain_report(self) -> dict:
        return {
            "drained": self.drained,
            "queued": len(self.sched.queue),
            "staged": sum(len(g.reqs) for g in self._staged),
            "computing": sum(len(g.reqs) for g in self._compute),
            "retry_pending": self.retry_pending,
            "occupancy": self.sched.occupancy,
            "health": self.health.state,
        }

    def run_until_done(self, max_steps: int = 100_000) -> dict:
        """Step until drained; returns the (empty) drain report.  Raises
        :class:`DrainTimeout` — with the report attached — if ``max_steps``
        elapse with work still in flight, so a hung engine fails loudly
        instead of silently vanishing requests."""
        for _ in range(max_steps):
            if self.drained:
                return self.drain_report()
            self.step()
        if self.drained:
            return self.drain_report()
        report = self.drain_report()
        raise DrainTimeout(
            f"engine not drained after {max_steps} steps: {report}", report)

    def export_state(self) -> dict:
        """Host-side snapshot of what a process-level restart must
        persist: the params (everything else — compiled buckets, packed
        slabs, plan cache — is rebuilt deterministically from them)."""
        return {"params": jax.device_get(self.params)}

    def reset_metrics(self):
        """Zero throughput/latency counters (e.g. after jit warmup) without
        touching queue, slots, compiled buckets, health state, or the
        packed-slab and admission state (a warmed service-time estimate is
        kept)."""
        self.latency = LatencyTracker(window=self.scfg.latency_window)
        self.images_submitted = 0
        self.images_completed = 0
        self.images_shed = 0
        self.images_expired = 0
        self.images_retried = 0
        self.images_within_slo = 0
        self.batches_run = 0
        self.batches_failed = 0
        self.bucket_counts = {}
        self.shed_reasons = {}
        self.sdc_detections = 0
        self.slab_integrity_failures = 0
        self.screen_nonfinite = 0
        self.screen_magnitude = 0
        self._t_serve = 0.0

    # ------------------------------------------------------------------
    @property
    def imgs_per_s(self) -> float:
        return self.images_completed / self._t_serve if self._t_serve else 0.0

    @property
    def goodput_imgs_per_s(self) -> float:
        """Within-SLO completions per serve-second (== img/s when no SLO
        is configured: every completion counts)."""
        if not self._t_serve:
            return 0.0
        good = (self.images_within_slo if self.scfg.slo_ms
                else self.images_completed)
        return good / self._t_serve

    def accounting(self) -> dict:
        """The fault-tolerance invariant, live: every submitted image is
        completed, shed, expired, or still in flight — nothing vanishes.
        Once drained, ``submitted == completed + shed + expired``."""
        in_flight = (len(self.sched.queue)
                     + sum(len(g.reqs) for g in self._staged)
                     + sum(len(g.reqs) for g in self._compute)
                     + self.retry_pending)
        accounted = (self.images_completed + self.images_shed
                     + self.images_expired + in_flight)
        return {
            "submitted": self.images_submitted,
            "completed": self.images_completed,
            "shed": self.images_shed,
            "expired": self.images_expired,
            "in_flight": in_flight,
            "balanced": self.images_submitted == accounted,
            # SDC screen verdicts, separated: rows rejected for
            # non-finiteness vs for busting the magnitude bound (both
            # retried, so neither breaks the balance above)
            "screen_nonfinite": self.screen_nonfinite,
            "screen_magnitude": self.screen_magnitude,
        }

    def stats(self) -> dict:
        return {
            "images_completed": self.images_completed,
            "images_shed": self.images_shed,
            "images_expired": self.images_expired,
            "images_retried": self.images_retried,
            "images_within_slo": (self.images_within_slo
                                  if self.scfg.slo_ms else None),
            "batches_run": self.batches_run,
            "batches_failed": self.batches_failed,
            "avg_occupancy": (self.images_completed / self.batches_run
                              if self.batches_run else 0.0),
            "bucket_counts": dict(sorted(self.bucket_counts.items())),
            "buckets": list(self.buckets),
            "bucket_resizes": list(self.policy.resizes) if self.policy else [],
            "imgs_per_s": self.imgs_per_s,
            "goodput_imgs_per_s": self.goodput_imgs_per_s,
            "latency_ms": self.latency.percentiles_ms(),
            "tuned_layers": sorted(self.plans),
            "health": self.health.stats(),
            "shed_reasons": dict(self.shed_reasons),
            "degraded_buckets": sorted(self._degraded),
            "degradations": list(self.degradations),
            "faults": self.faults.summary() if self.faults else None,
            "sdc": {
                "abft_armed": self._abft,
                "verify_slabs": self.scfg.verify_slabs,
                "detections": self.sdc_detections,
                "slab_integrity_failures": self.slab_integrity_failures,
                "screen_nonfinite": self.screen_nonfinite,
                "screen_magnitude": self.screen_magnitude,
            },
            "accounting": self.accounting(),
        }
