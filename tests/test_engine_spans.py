"""CnnEngine's profiler spans, its admission stamp, and the layer names the
model gives its ops and kernels.

A few ``step()``s of a reduced AlexNet run under ``jax.profiler`` (host
tracer at level 1, as the benchmark traces); the ``cnn.*`` events are read
back from the ``.xplane.pb``.  The spans' nesting and stats are what the
benchmark's reduction (``bench/cnn_spans.py``) relies on.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import alexnet
from repro.serving import CnnEngine, CnnServeConfig, ImageRequest

PHASES = ("cnn.stage", "cnn.put", "cnn.launch", "cnn.fetch", "cnn.retire")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Six requests on a cold engine with ``max_batch`` 4: a bucket-4 and
    a bucket-2 group, each bucket compiled on first use inside the
    trace."""
    cfg = get_config("alexnet").reduced()
    params = alexnet.init(jax.random.PRNGKey(0), cfg)
    eng = CnnEngine(cfg, CnnServeConfig(max_batch=4), params=params)
    rng = np.random.default_rng(0)
    reqs = [ImageRequest(image=rng.standard_normal(
        (cfg.image_size, cfg.image_size, cfg.in_channels)).astype(np.float32))
        for _ in range(6)]
    for r in reqs:
        eng.submit(r)
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        eng.run_until_done(max_steps=20)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("cnn.")]
    return eng, reqs, sorted(events, key=lambda e: e[1])


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_phases_lie_inside_a_step(traced):
    _, _, events = traced
    steps = _named(events, "cnn.step")
    assert steps
    for name in PHASES:
        found = _named(events, name)
        assert found, name
        for _, s, e, _ in found:
            assert any(a <= s and e <= b for _, a, b, _ in steps), name
    for _, s, e, _ in _named(events, "cnn.put"):
        assert any(a <= s and e <= b
                   for _, a, b, _ in _named(events, "cnn.stage"))


def test_fetch_carries_an_earlier_launchs_batch(traced):
    _, _, events = traced
    launches = _named(events, "cnn.launch")
    fetches = _named(events, "cnn.fetch")
    assert len(fetches) == len(launches) == 2
    for _, s, _, st in fetches:
        assert any(ls["batch"] == st["batch"] and le <= s
                   for _, _, le, ls in launches)
    assert sorted(st["bucket"] for *_, st in launches) == [2, 4]


def test_put_carries_each_requests_queue_wait(traced):
    eng, reqs, events = traced
    puts = {st["batch"]: str(st["queue_wait_us"]).split()
            for *_, st in _named(events, "cnn.put")}
    assert sorted(len(w) for w in puts.values()) == [2, 4]
    waits = sorted(int(w) for ws in puts.values() for w in ws)
    want = sorted(round((r.t_admit - r.t_submit) * 1e6) for r in reqs)
    assert waits == want


def test_first_use_of_a_bucket_compiles_once(traced):
    eng, _, events = traced
    compiles = _named(events, "cnn.compile")
    assert sorted(st["bucket"] for *_, st in compiles) == [2, 4]
    assert sorted(eng.compile_seconds) == [2, 4]
    for _, s, e, _ in compiles:        # compiled at launch, inside its span
        assert any(a <= s and e <= b
                   for _, a, b, _ in _named(events, "cnn.launch"))


def test_served_request_stamps_are_ordered(traced):
    _, reqs, _ = traced
    for r in reqs:
        assert r.done
        assert r.t_submit <= r.t_admit <= r.t_done


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations call
    (not into a Pallas kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    yield from _eqns(inner)


@pytest.mark.parametrize("hoisted", [False, True], ids=["staged", "hoisted"])
def test_layers_carry_their_names(hoisted):
    """Each layer's ops sit under its named scope (``conv1``.., ``fc6``..)
    and each Pallas kernel is named by its layer (``conv3_winograd``)."""
    cfg = dataclasses.replace(get_config("alexnet").reduced(),
                              use_pallas=True)
    params = alexnet.init(jax.random.PRNGKey(0), cfg)
    x = jax.ShapeDtypeStruct((2, cfg.image_size, cfg.image_size, 3),
                             np.float32)
    if hoisted:
        packed = alexnet.pack_serving_slabs(params, cfg, 2)
        jaxpr = jax.make_jaxpr(lambda p, s, x: alexnet.apply(
            p, cfg, x, packed=s))(params, packed, x)
    else:
        jaxpr = jax.make_jaxpr(lambda p, x: alexnet.apply(p, cfg, x))(
            params, x)
    scopes = {str(e.source_info.name_stack).split("/")[0]
              for e in jaxpr.jaxpr.eqns}
    n_conv = len(cfg.conv_channels)
    assert ({f"conv{i + 1}" for i in range(n_conv)}
            | {"fc6", "fc7", "fc8"}) <= scopes
    kernels = sorted(e.params["name"]
                     for e in _eqns(jaxpr.jaxpr)
                     if e.primitive.name == "pallas_call")
    assert kernels == ["conv1_direct", "conv2_direct", "conv3_winograd",
                       "conv4_winograd", "conv5_winograd"]
