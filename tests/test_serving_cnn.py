"""CnnEngine: bucket-padding bit-exactness, counters, mixed arrival, DP.

The adversarial core: served logits must *bit-match* a direct
``alexnet.apply`` on the same images for every bucket padding — a single
request (bucket 1), a partial bucket (3 requests padded to 4), and a full
``max_batch`` — so batching/padding can never change what a user gets back.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import alexnet
from repro.serving import (CnnEngine, CnnServeConfig, FaultInjector,
                           FaultSpec, ImageRequest, SlotScheduler,
                           bucket_sizes)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def served():
    """One reduced config + params + jitted direct-apply oracle."""
    cfg = get_config("alexnet").reduced()
    params = alexnet.init(jax.random.PRNGKey(0), cfg)
    ref = jax.jit(lambda p, x: alexnet.apply(p, cfg, x))
    return cfg, params, lambda x: ref(params, x)


def _images(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    ).astype(np.float32)


def test_bucket_sizes():
    assert bucket_sizes(1) == (1,)
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(6) == (1, 2, 4, 6)      # non-pow2 cap kept as-is


@pytest.mark.parametrize("n_req,max_batch", [
    (1, 4),    # bucket 1: single request
    (3, 4),    # partial bucket: padded 3 -> 4
    (4, 4),    # full max_batch bucket
])
def test_served_logits_bitmatch_direct_apply(served, n_req, max_batch):
    """Bucket padding must never perturb logits: exact array equality."""
    cfg, params, ref = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=max_batch), params=params)
    imgs = _images(cfg, n_req, seed=n_req)
    reqs = [ImageRequest(image=imgs[i]) for i in range(n_req)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    expect = np.asarray(ref(jnp.asarray(imgs)))
    got = np.stack([r.logits for r in reqs])
    assert np.array_equal(got, expect), \
        np.abs(got - expect).max()
    assert all(r.done and r.label == int(expect[i].argmax())
               for i, r in enumerate(reqs))
    # the padded bucket really was used (3 -> 4), not an exact-shape compile
    if n_req == 3:
        assert eng.bucket_counts == {4: 1}


def test_counters_consistent(served):
    """Occupancy/throughput accounting adds up across multiple groups."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4), params=params)
    reqs = [ImageRequest(image=im) for im in _images(cfg, 6, seed=9)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    s = eng.stats()
    assert s["images_completed"] == 6
    assert eng.sched.submitted == eng.sched.completed == 6
    assert eng.sched.occupancy == 0 and eng.sched.idle
    # 6 requests over max_batch=4 slots*depth -> groups of 4 and 2
    assert s["batches_run"] == 2
    assert s["bucket_counts"] == {2: 1, 4: 1}
    assert sum(k * v for k, v in s["bucket_counts"].items()) >= 6
    assert s["avg_occupancy"] == pytest.approx(3.0)
    # every staged shape came from the declared bucket set (bounded jit)
    assert set(s["bucket_counts"]) <= set(eng.buckets)
    assert s["imgs_per_s"] > 0
    lat = s["latency_ms"]
    assert len(eng.latency) == 6
    assert 0 < lat["p50"] <= lat["p90"] <= lat["p99"]


def test_mixed_arrival_retires_correctly(served):
    """Shuffled submissions across several groups: each request gets *its*
    logits (per-image oracle), FIFO admission order, uids intact."""
    cfg, params, ref = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=2), params=params)
    imgs = _images(cfg, 7, seed=3)
    order = [4, 0, 6, 2, 5, 1, 3]
    reqs = {i: ImageRequest(image=imgs[i]) for i in order}
    for i in order:
        eng.submit(reqs[i])
    eng.run_until_done()
    assert all(r.done for r in reqs.values())
    # groups of (2,2,2,1) in arrival order
    assert eng.stats()["bucket_counts"] == {1: 1, 2: 3}
    for i in order:
        expect = np.asarray(ref(jnp.asarray(imgs[i][None])))[0]
        np.testing.assert_allclose(reqs[i].logits, expect,
                                   rtol=1e-5, atol=1e-6)
        assert reqs[i].label == int(expect.argmax())
    # latency ordering: earlier-arriving requests never finish after
    # later ones (FIFO groups retire in admission order)
    times = [reqs[i].t_done for i in order]
    assert times == sorted(times)


def test_incremental_submission_reuses_buckets(served):
    """Requests arriving between steps are admitted mid-flight and only
    compile shapes from the declared bucket set."""
    cfg, params, ref = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4), params=params)
    imgs = _images(cfg, 5, seed=11)
    reqs = [ImageRequest(image=im) for im in imgs]
    eng.submit(reqs[0])
    eng.step()                      # group of 1 in flight
    for r in reqs[1:]:
        eng.submit(r)
    eng.run_until_done()
    assert all(r.done for r in reqs)
    assert set(eng.bucket_counts) <= {1, 2, 4}
    expect = np.asarray(ref(jnp.asarray(imgs)))
    for i, r in enumerate(reqs):
        np.testing.assert_allclose(r.logits, expect[i], rtol=1e-5, atol=1e-6)


def test_submit_rejects_wrong_image_shape(served):
    """Shape errors surface at the API boundary, not via silent numpy
    broadcasting deep inside staging."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=2), params=params)
    bad = [np.zeros((1, cfg.image_size, 3), np.float32),          # broadcastable
           np.zeros((cfg.image_size, cfg.image_size), np.float32),
           np.zeros((cfg.image_size + 1, cfg.image_size, 3), np.float32)]
    for img in bad:
        with pytest.raises(ValueError, match="image shape"):
            eng.submit(ImageRequest(image=img))
    assert eng.sched.submitted == 0


def test_slot_scheduler_invariants():
    """Shared core: FIFO admission, limit, retire bookkeeping."""
    s = SlotScheduler(3)
    for i in range(5):
        s.submit(f"r{i}")
    assert s.submitted == 5 and not s.idle
    got = s.admit(limit=2)
    assert [(0, "r0"), (1, "r1")] == got
    assert s.occupancy == 2 and s.active.tolist() == [True, True, False]
    assert s.admit() == [(2, "r2")]
    assert s.admit() == []                      # full
    assert s.retire(1) == "r1"
    assert s.completed == 1
    assert s.admit() == [(1, "r3")]             # freed slot reused FIFO
    assert s.retire(0) == "r0"
    with pytest.raises(AssertionError):
        s.retire(0)                             # double retire must assert


def test_data_parallel_bitmatch_subprocess(served):
    """DP sharding over forced host devices must not change served logits
    (divisible bucket sharded, indivisible bucket replicated)."""
    del served  # subprocess re-creates state; fixture just orders tests
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import alexnet
        from repro.serving import CnnEngine, CnnServeConfig, ImageRequest
        assert jax.device_count() == 2
        cfg = get_config("alexnet").reduced()
        params = alexnet.init(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        imgs = rng.standard_normal(
            (5, cfg.image_size, cfg.image_size, 3)).astype(np.float32)
        eng = CnnEngine(cfg, CnnServeConfig(max_batch=4, data_parallel=True),
                        params=params)
        assert eng.mesh is not None and eng.mesh.devices.size == 2
        reqs = [ImageRequest(image=im) for im in imgs]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()          # groups: 4 (sharded), 1 (replicated)
        assert all(r.done for r in reqs)
        ref = np.asarray(jax.jit(
            lambda p, x: alexnet.apply(p, cfg, x))(params, jnp.asarray(imgs)))
        got = np.stack([r.logits for r in reqs])
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def _staging_case(bucket: int, data_parallel: bool):
    """Serve one full bucket and check what crossed to the device: the
    NHWC batch (split into whole images under data parallelism), logits
    bit-equal to a jitted ``apply`` on each device's slice, and
    ``stage.corrupt`` NaNs in row 0 of the staged copy alone."""
    cfg = get_config("alexnet").reduced()
    params = alexnet.init(jax.random.PRNGKey(0), cfg)
    fwd = jax.jit(lambda p, x: alexnet.apply(p, cfg, x))
    ref = lambda x: fwd(params, x)                          # noqa: E731
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4,
                                        data_parallel=data_parallel),
                    params=params)
    hw, c = cfg.image_size, cfg.in_channels
    imgs = _images(cfg, bucket, seed=bucket)
    k = bucket // eng._shards(bucket)           # images on each device
    expect = np.concatenate([np.asarray(ref(imgs[i:i + k]))
                             for i in range(0, bucket, k)])

    def staged(reqs):
        for r in reqs:
            eng.submit(r)
        eng._stage()
        images = eng._staged[-1].images
        assert images.shape == (bucket, hw, hw, c)
        assert {s.data.shape for s in images.addressable_shards} == {
            (k, hw, hw, c)}
        return np.asarray(images)

    reqs = [ImageRequest(image=im) for im in imgs]
    assert np.array_equal(staged(reqs), imgs)
    eng.run_until_done()
    assert np.array_equal(np.stack([r.logits for r in reqs]), expect)

    eng.arm_faults(FaultInjector(0, {"stage.corrupt": FaultSpec(at=(0,))}))
    reqs = [ImageRequest(image=im) for im in imgs]
    host = staged(reqs)
    assert np.isnan(host[0]).all()
    assert np.array_equal(host[1:], imgs[1:])
    eng.run_until_done()
    assert all(r.done for r in reqs)
    assert [r.attempts for r in reqs] == [1] + [0] * (bucket - 1)
    # rows 1.. retire from the corrupted batch; row 0 re-staged alone
    assert all(np.array_equal(r.logits, e)
               for r, e in zip(reqs[1:], expect[1:]))
    assert np.array_equal(reqs[0].logits, np.asarray(ref(imgs[:1]))[0])


@pytest.mark.parametrize("data_parallel", [False, True],
                         ids=["one_device", "data_parallel"])
@pytest.mark.parametrize("bucket", [1, 2, 4])
def test_staged_batch_serves_bit_equal(bucket, data_parallel):
    """Under data parallelism on two forced host devices (bucket 1
    replicated, 2 and 4 split), in a subprocess like the test above."""
    if not data_parallel:
        _staging_case(bucket, False)
        return
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {os.path.join(ROOT, "tests")!r})
        import jax
        assert jax.device_count() == 2
        from test_serving_cnn import _staging_case
        _staging_case({bucket}, True)
        print("OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout


def _as_layout(img, layout):
    """The same image values held in another memory order or dtype."""
    if layout == "hwc":
        return np.ascontiguousarray(img)
    if layout == "chw":                 # a planar image, transposed
        return np.ascontiguousarray(img.transpose(2, 0, 1)).transpose(1, 2, 0)
    if layout == "pool":                # one image of an (H, C, N, W) pool
        pool = np.stack([img, img[::-1]]).transpose(1, 3, 0, 2).copy()
        return pool.transpose(2, 0, 3, 1)[0]
    return img.astype(np.float64)


@pytest.fixture(scope="module")
def bucket1(served):
    cfg, params, _ = served
    return CnnEngine(cfg, CnnServeConfig(max_batch=1), params=params)


@pytest.mark.parametrize("layout", ["hwc", "chw", "pool", "float64"])
def test_image_memory_order_does_not_change_what_is_staged(served, bucket1,
                                                           layout):
    """An image whose channels are planes is copied a plane at a time;
    what crosses and what is served are those of its C-ordered float32
    copy, bit for bit."""
    cfg, _, ref = served
    img = _images(cfg, 1, seed=21)[0]
    src = _as_layout(img, layout)
    assert np.array_equal(src, img)
    assert src.flags.c_contiguous == (layout in ("hwc", "float64"))
    eng = bucket1
    req = ImageRequest(image=src)
    eng.submit(req)
    eng._stage()
    staged = np.asarray(eng._staged[-1].images)
    assert np.array_equal(staged, img.reshape(staged.shape))
    eng.run_until_done()
    assert np.array_equal(req.logits, np.asarray(ref(img[None]))[0])


def test_lowering_error_raises_out_of_engine(served, monkeypatch):
    """A forward that does not lower (a kernel the compiler refuses) is a
    datapath defect: it raises out of the engine and never enters the
    retry / degradation ladder that exists for runtime faults."""
    cfg, params, _ = served
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=2, degrade_threshold=1),
                    params=params)

    def refused(*args, **kwargs):
        raise NotImplementedError("Mosaic refused the kernel")

    monkeypatch.setattr(alexnet, "features", refused)
    eng.submit(ImageRequest(image=_images(cfg, 1)[0]))
    with pytest.raises(NotImplementedError, match="Mosaic refused"):
        eng.run_until_done()
    with pytest.raises(NotImplementedError, match="Mosaic refused"):
        eng.precompile()
    assert eng.degradations == [] and eng.batches_failed == 0
    assert eng.health.state == "healthy"
    assert eng.executables == {}
