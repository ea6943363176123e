"""Serving fleet: SLO policy, admission shedding, multi-model registry,
pack-once slabs — plus the serving-layer bug-sweep regressions (bounded
latency tracker, staging dtype, bucket_for contract)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import alexnet
from repro.serving import (AdmissionController, CnnEngine, CnnServeConfig,
                           DynamicBucketPolicy, ImageRequest, LatencyTracker,
                           ModelRegistry)


@pytest.fixture(scope="module")
def served():
    cfg = get_config("alexnet").reduced()
    params = alexnet.init(jax.random.PRNGKey(0), cfg)
    ref = jax.jit(lambda p, x: alexnet.apply(p, cfg, x))
    return cfg, params, lambda x: ref(params, x)


def _images(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    ).astype(np.float32)


# ---------------------------------------------------------------------------
# bug sweep regressions
# ---------------------------------------------------------------------------
def test_latency_tracker_bounded():
    """The tracker is a ring buffer: unbounded request streams must not
    grow host memory, while percentiles track the recent window."""
    t = LatencyTracker(window=64)
    for _ in range(1000):
        t.record(1.0)                   # old regime: 1000 ms latencies
    for _ in range(64):
        t.record(0.001)                 # recent regime: 1 ms
    assert len(t) == 64                 # bounded, not 1064
    assert t.total == 1064              # lifetime count still exact
    p = t.percentiles_ms()
    assert p["p99"] == pytest.approx(1.0, rel=0.1)   # old spikes aged out
    assert p["p50"] <= p["p90"] <= p["p99"]


def test_latency_tracker_window_shorter_than_stream():
    t = LatencyTracker(window=4)
    for ms in (1, 2, 3, 4, 5, 6):
        t.record(ms / 1e3)
    assert len(t) == 4 and t.total == 6
    assert t.percentiles_ms()["p50"] == pytest.approx(4.5, rel=0.05)


def test_bucket_for_rejects_oversized_group(served):
    """A group larger than max_batch must raise, not silently pad to an
    undeclared bucket shape (which would jit-compile off-ladder)."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4), params=params)
    assert eng.bucket_for(3) == 4 and eng.bucket_for(4) == 4
    with pytest.raises(ValueError, match="exceeds max_batch"):
        eng.bucket_for(5)


def test_staging_buffer_uses_config_dtype(served):
    """The staged H2D buffer must carry the model's dtype — a bf16 model
    silently fed fp32 doubles the §3.5 stream-buffer bytes."""
    cfg, params, _ = served
    eng32 = CnnEngine(cfg, CnnServeConfig(max_batch=2), params=params)
    assert eng32._buf_dtype == jnp.dtype("float32")

    cfg16 = dataclasses.replace(cfg, dtype="bfloat16")
    eng16 = CnnEngine(cfg16, CnnServeConfig(max_batch=2), seed=0)
    assert eng16._buf_dtype == jnp.dtype(jnp.bfloat16)
    imgs = _images(cfg16, 2, seed=5)
    reqs = [ImageRequest(image=im) for im in imgs]
    for r in reqs:
        eng16.submit(r)
    eng16.run_until_done()
    ref = np.asarray(jax.jit(lambda p, x: alexnet.apply(p, cfg16, x))(
        eng16.params, jnp.asarray(imgs)), np.float32)
    got = np.stack([np.asarray(r.logits, np.float32) for r in reqs])
    scale = np.abs(ref).max() + 1e-9
    assert np.abs(got - ref).max() / scale < 5e-2   # bf16 tolerance
    assert all(r.done for r in reqs)


# ---------------------------------------------------------------------------
# SLO policy units
# ---------------------------------------------------------------------------
def test_dynamic_bucket_policy_inserts_dominant_size():
    pol = DynamicBucketPolicy(8, slo_ms=5.0, max_extra=2, min_samples=8)
    assert pol.buckets() == (1, 2, 4, 8)
    for _ in range(8):
        pol.observe_admit(6)            # bursts of 6 padded to 8 (25% waste)
        pol.observe_latency(0.010)      # 10ms > 5ms SLO
    assert pol.maybe_resize() == 6
    assert pol.buckets() == (1, 2, 4, 6, 8)
    assert pol.resizes == [6]


def test_dynamic_bucket_policy_noop_within_slo():
    pol = DynamicBucketPolicy(8, slo_ms=50.0, min_samples=4)
    for _ in range(8):
        pol.observe_admit(6)
        pol.observe_latency(0.010)      # 10ms < 50ms SLO: healthy
    assert pol.maybe_resize() is None
    assert pol.buckets() == (1, 2, 4, 8)


def test_dynamic_bucket_policy_bounded_insertions():
    pol = DynamicBucketPolicy(8, slo_ms=1.0, max_extra=1, min_samples=2)
    for size in (6, 3):
        for _ in range(8):
            pol.observe_admit(size)
            pol.observe_latency(0.050)
        pol.maybe_resize()
    assert pol.extra == [6]             # second insert refused: max_extra=1
    assert len(pol.buckets()) == len((1, 2, 4, 8)) + 1


def test_dynamic_bucket_policy_skips_small_padding():
    """7->8 pads 12.5% < pad_frac: not worth an extra compiled shape."""
    pol = DynamicBucketPolicy(8, slo_ms=1.0, min_samples=2, pad_frac=0.2)
    for _ in range(8):
        pol.observe_admit(7)
        pol.observe_latency(0.050)
    assert pol.maybe_resize() is None


def test_admission_controller_sheds_on_backlog():
    adm = AdmissionController(slo_ms=10.0, slack=1.0)
    assert adm.admit(10 ** 6)           # no estimate yet: admit everything
    adm.observe_batch(4, 0.008)         # 2ms per image
    assert adm.t_img_ms == pytest.approx(2.0)
    assert adm.admit(5)                 # 10ms wait == budget: still in
    assert not adm.admit(6)             # 12ms wait: shed
    assert adm.estimated_wait_ms(6) == pytest.approx(12.0)


def test_engine_sheds_and_reports(served):
    """Shed requests are *reported* (False + req.shed + counter), never
    silently dropped, and never occupy a slot or produce logits."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=2, slo_ms=1.0,
                                        admission=True), params=params)
    eng.admission.observe_batch(1, 1.0)     # 1000ms/img: anything queued busts
    ok = ImageRequest(image=_images(cfg, 1, seed=1)[0])
    assert eng.try_submit(ok)               # empty queue: 0 wait, admitted
    shed = ImageRequest(image=_images(cfg, 1, seed=2)[0])
    assert not eng.try_submit(shed)         # 1 image backlog > 1ms SLO
    assert shed.shed and not shed.done
    assert eng.images_shed == 1
    eng.run_until_done()
    assert ok.done and ok.logits is not None
    assert not shed.done and shed.logits is None
    s = eng.stats()
    assert s["images_shed"] == 1 and s["images_completed"] == 1
    assert eng.sched.submitted == 1         # shed never reached the queue


def test_arm_slo_on_live_engine(served):
    """SLO control plane attaches after warmup without losing compiled
    buckets or counters (calibrated-SLO deployment path)."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=2), params=params)
    assert eng.policy is None and eng.admission is None
    for r in [ImageRequest(image=im) for im in _images(cfg, 2, seed=3)]:
        eng.submit(r)
    eng.run_until_done()
    compiled = dict(eng.executables)
    assert compiled
    eng.arm_slo(50.0, dynamic_buckets=True, admission=True)
    assert eng.policy is not None and eng.admission is not None
    assert eng.scfg.slo_ms == 50.0
    assert eng.executables == compiled      # warm state survives
    assert eng.images_completed == 2
    eng.arm_slo(None)                       # disarm
    assert eng.policy is None and eng.admission is None


def test_goodput_accounting(served):
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4, slo_ms=10_000.0),
                    params=params)
    for r in [ImageRequest(image=im) for im in _images(cfg, 4, seed=4)]:
        eng.submit(r)
    eng.run_until_done()
    s = eng.stats()
    assert s["images_within_slo"] == 4      # 10s SLO: everything makes it
    assert s["goodput_imgs_per_s"] == pytest.approx(s["imgs_per_s"])


# ---------------------------------------------------------------------------
# pack-once hoisted slabs
# ---------------------------------------------------------------------------
def test_pack_once_slabs_bitmatch_and_reuse(served):
    """apply(packed=pack_serving_slabs(...)) must bit-match the plain
    forward at the same batch, and the engine must pack each bucket shape
    exactly once (slabs are reused jit arguments, not re-packed)."""
    cfg, params, ref = served
    imgs = jnp.asarray(_images(cfg, 4, seed=7))
    packed = alexnet.pack_serving_slabs(params, cfg, 4)
    got = jax.jit(lambda p, s, x: alexnet.apply(p, cfg, x, packed=s))(
        params, packed, imgs)
    assert np.array_equal(np.asarray(got), np.asarray(ref(imgs)))

    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4), params=params)
    assert eng._hoist
    first = eng._slabs(4)
    assert eng._slabs(4) is first           # cached, not re-packed
    for r in [ImageRequest(image=im) for im in _images(cfg, 4, seed=8)]:
        eng.submit(r)
    eng.run_until_done()
    assert eng._slabs(4) is first and set(eng._packed) == {4}


# ---------------------------------------------------------------------------
# multi-model registry
# ---------------------------------------------------------------------------
def test_registry_two_models_interleaved():
    """AlexNet + VGG-16 served concurrently through one registry: each
    request's logits bit-match its own model's direct apply, and the
    per-model counters stay consistent under interleaved submission."""
    reg = ModelRegistry(slot_budget=16)
    cfgs, refs = {}, {}
    for name in ("alexnet", "vgg16"):
        cfg = get_config(name).reduced()
        eng = reg.register(name, cfg, CnnServeConfig(max_batch=4))
        cfgs[name] = cfg
        refs[name] = jax.jit(
            lambda p, x, c=cfg: alexnet.apply(p, c, x)), eng.params
    imgs = {"alexnet": _images(cfgs["alexnet"], 3, seed=10),
            "vgg16": _images(cfgs["vgg16"], 2, seed=11)}
    reqs = {n: [ImageRequest(image=im) for im in imgs[n]] for n in imgs}
    for pair in zip(reqs["alexnet"], reqs["vgg16"]):    # interleave models
        for r, n in zip(pair, ("alexnet", "vgg16")):
            assert reg.submit(n, r)
    assert reg.submit("alexnet", reqs["alexnet"][2])
    reg.run_until_done()

    for n in ("alexnet", "vgg16"):
        ref, params = refs[n]
        expect = np.asarray(ref(params, jnp.asarray(imgs[n])))
        got = np.stack([r.logits for r in reqs[n]])
        assert np.array_equal(got, expect), (n, np.abs(got - expect).max())
    s = reg.stats()
    assert s["models"]["alexnet"]["images_completed"] == 3
    assert s["models"]["vgg16"]["images_completed"] == 2
    assert s["fleet"]["images_completed"] == 5
    assert s["fleet"]["images_shed"] == 0
    assert s["fleet"]["slots_used"] == 16 and reg.idle
    for n in ("alexnet", "vgg16"):
        e = reg[n]
        assert e.sched.submitted == e.sched.completed == len(reqs[n])
        assert e.sched.occupancy == 0


def test_registry_enforces_slot_budget():
    cfg = get_config("alexnet").reduced()
    reg = ModelRegistry(slot_budget=20)
    reg.register("a", cfg, CnnServeConfig(max_batch=8))     # 16 slots
    with pytest.raises(ValueError, match="slots"):
        reg.register("b", cfg, CnnServeConfig(max_batch=4))  # needs 8 > 4 left
    reg.register("c", cfg, CnnServeConfig(max_batch=2))     # 4 slots: fits
    assert reg.slots_used == 20
    with pytest.raises(ValueError, match="already registered"):
        reg.register("a", cfg, CnnServeConfig(max_batch=1))
    with pytest.raises(KeyError, match="unknown model"):
        reg.submit("nope", ImageRequest(image=_images(cfg, 1)[0]))


# ---------------------------------------------------------------------------
# traffic generators (benchmarks/serve_fleet.py)
# ---------------------------------------------------------------------------
def test_trace_generators():
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from benchmarks.serve_fleet import (bursty_trace, diurnal_trace,
                                        poisson_trace)
    rng = np.random.default_rng(0)
    p = poisson_trace(100.0, 2.0, rng)
    assert p == sorted(p) and all(0 <= t < 2.0 for t in p)
    assert 100 < len(p) < 300           # ~200 expected

    b = bursty_trace(5, 6, 0.1, np.random.default_rng(1))
    assert len(b) == 30 and b == sorted(b)
    assert b[:6] == [0.0] * 6           # first burst lands together

    d = diurnal_trace(100.0, 2.0, 1.0, np.random.default_rng(2))
    assert d == sorted(d) and all(0 <= t < 2.0 for t in d)
    assert len(d) > 50
    # same seed -> same trace (benchmark reproducibility)
    d2 = diurnal_trace(100.0, 2.0, 1.0, np.random.default_rng(2))
    assert d == d2
