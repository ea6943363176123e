"""CPU tests of the benchmark harness.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The yardstick (``work.py``, ``trace.py``, the traffic generators), the
discovery of cells, configurations, traffic and metrics by name, the
refusal to run without a chip, and whole runs at a CPU-test size
(``data/<config>_tiny.json``, Pallas kernels in interpret mode) that skip
only the look for a chip: the three-pass bfloat16 control must fail the
full-size configuration's ``logit_err`` limit that the program passes,
and a run whose served answers are altered where they are produced must
come out not ``correct``.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace as tr  # noqa: E402
import work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _cfg(name):
    return run.config_file(name)


# ---------------------------------------------------------------------------
# work.py
# ---------------------------------------------------------------------------
def test_alexnet_layer_counts():
    layers = {w.name: w for w in work.layer_work(_cfg("alexnet"))}
    assert layers["conv3"].direct_macs == 149_520_384
    assert layers["conv3"].least_macs == 56_623_104      # F(4,3), 4x4 tiles
    assert layers["conv1"].least_macs == 105_415_200     # 11x11 s4: direct
    assert layers["conv2"].least_macs == 223_948_800     # 5x5 g2: direct
    conv = sum(w.least_macs for w in layers.values() if w.op == "conv")
    fc = sum(w.least_macs for w in layers.values() if w.op == "fc")
    assert (conv, fc) == (456_765_984, 58_621_952)
    assert work.image_flops(_cfg("alexnet")) == 2 * (conv + fc)
    fc_w = sum(w.weight_bytes for w in layers.values() if w.op == "fc")
    assert fc_w == 234_524_576                            # float32
    # conv5 writes its pooled 6x6x256 map; fc6 reads 9216 features
    assert layers["conv5"].out_bytes == 6 * 6 * 256 * 4
    assert layers["fc6"].in_bytes == 9216 * 4


def test_vgg16_layer_counts():
    layers = work.layer_work(_cfg("vgg16"))
    assert len(layers) == 16
    conv = sum(w.least_macs for w in layers if w.op == "conv")
    fc = sum(w.least_macs for w in layers if w.op == "fc")
    assert (conv, fc) == (3_942_825_984, 123_633_664)
    assert 2 * sum(w.direct_macs for w in layers) == 30_940_528_640
    assert sum(w.weight_bytes for w in layers if w.op == "fc") == 494_571_424


def test_least_seconds_takes_the_larger_bound():
    w = work.LayerWork("x", "fc", 10, 10, 4, 4, 1000)
    assert w.least_seconds(2, 1.0, 1e9) == pytest.approx(40.0)   # compute
    assert w.least_seconds(2, 1e12, 1.0) == pytest.approx(1016.0)  # bytes


# ---------------------------------------------------------------------------
# trace.py
# ---------------------------------------------------------------------------
_XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 4000 duration_ps: 4000 }
    events { metadata_id: 2 offset_ps: 10000 duration_ps: 2000 }
    events { metadata_id: 1 offset_ps: 25000 duration_ps: 1000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 30000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "custom-call.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_forward" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.9" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 9000 }
    events { metadata_id: 3 offset_ps: 9000 duration_ps: 6000 }
    events { metadata_id: 4 offset_ps: 1000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.engine.step" } }
  event_metadata { key: 3 value { id: 3 name: "bench.client.wait" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction(fwd)" } } }
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_trace_reduction_known_numbers():
    """Window [0, 20) ns; ops [0,5) and [4,8) overlap, a kernel at
    [10,12), an op past the window; host: step [0,9), wait [9,15)."""
    device, host = tr.load(_profile(_XSPACE), chips=1)
    assert len(device) == 1 and len(device[0]) == 4
    assert {n for n, _, _ in host} == {"bench.window", "bench.engine.step",
                                       "bench.client.wait"}
    r = tr.reduce(device, host, {"custom-call.2"})
    assert r.window_s == pytest.approx(20e-9)
    assert r.busy_s == pytest.approx(10e-9)
    assert r.kernel_s == pytest.approx(2e-9)
    assert r.xla_s == pytest.approx(9e-9)                 # 5 + 4, summed
    assert r.idle_share == pytest.approx(0.5)
    assert r.idle_by_span == pytest.approx(
        {"engine.step": 1e-9, "client.wait": 4e-9, "other": 5e-9})
    assert r.idle_in_step_s == pytest.approx(1e-9)
    b = tr.breakdown(r)
    assert b["device_ops"][0] == ["xla:fusion.1", pytest.approx(9e-9)]
    assert b["device_ops"][1] == ["pallas:custom-call.2",
                                  pytest.approx(2e-9)]
    assert [n for n, _ in b["idle_gaps"]] == ["other", "client.wait",
                                              "engine.step"]


def test_trace_reduction_averages_chips():
    device, host = tr.load(_profile(_XSPACE), chips=2)
    r = tr.reduce(device, host, set())
    assert r.busy_s == pytest.approx((10e-9 + 20e-9) / 2)


def _timeline_busy(ops, lo, hi):
    """Busy ns by marking a 1 ns timeline: an independent count."""
    t = np.zeros(int(hi - lo), bool)
    for _, s, e in ops:
        a, b = int(max(s, lo) - lo), int(min(e, hi) - lo)
        if b > a:
            t[a:b] = True
    return int(t.sum())


def test_trace_reduction_on_a_chip_slice():
    """A 30 ms slice of a traced ``alexnet.closed`` window on a v5e,
    reduced, against an independent count on a 1 ns timeline."""
    with open(os.path.join(BENCH, "testdata", "v5e_alexnet_slice.json")) as f:
        sl = json.load(f)
    lo, hi = sl["window"]
    device = [[(n, s - lo, e - lo) for n, s, e in sl["device"]]]
    host = ([("bench.window", 0, hi - lo)]
            + [(n, s - lo, e - lo) for n, s, e in sl["host"]])
    kernels = set(sl["kernel_names"])
    r = tr.reduce(device, host, kernels)
    busy = _timeline_busy(device[0], 0, hi - lo)
    assert r.busy_s * 1e9 == pytest.approx(busy, abs=len(device[0]) + 1)
    kern = _timeline_busy([o for o in device[0]
                           if 'custom_call_target="tpu_custom_call"' in o[0]],
                          0, hi - lo)
    assert r.kernel_s * 1e9 == pytest.approx(kern, abs=len(device[0]) + 1)
    assert r.kernel_s > 0 and r.xla_s > 0
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-9, abs=1e-12)
    assert sl["expected"] == pytest.approx(
        {"busy_s": r.busy_s, "kernel_s": r.kernel_s, "xla_s": r.xla_s,
         "idle_in_step_s": r.idle_in_step_s}, rel=1e-9)


# ---------------------------------------------------------------------------
# BENCHMARK.json and discovery by name
# ---------------------------------------------------------------------------
def test_benchmark_json_shape():
    bm = run.benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51
    names = [c["name"] for c in bm["configs"]]
    cells = [w["name"] for w in bm["workloads"]]
    metrics = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= 1
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:            # each cell reports what it moves
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for w in cells:
        c = run.cell(w)
        assert {m["name"] for m in c.end_to_end} > {"setup_s"}
        assert c.per_layer


@pytest.mark.parametrize("name", [w["name"] for w in
                                  run.benchmark()["workloads"]])
def test_cell_found_by_name(name):
    c = run.cell(name)
    assert c.config["model"] in ("alexnet", "vgg16")
    kind = run.traffic_kind(c.traffic["kind"])
    assert callable(kind.drive)
    for m in c.per_layer:
        assert callable(run.metric_reader(m["name"]).read)


def test_unknown_names_fail():
    with pytest.raises(KeyError):
        run.cell("no.such.cell")
    with pytest.raises(FileNotFoundError):
        run.metric_reader("no.such.metric")
    with pytest.raises(FileNotFoundError):
        run.traffic_kind("no_such_kind")


def test_configs_match_the_program():
    """Each configuration file is the network the program runs."""
    import serve

    for name in ("alexnet", "vgg16"):
        cfg = _cfg(name)
        pcfg = serve.program_config(cfg)
        assert pcfg.use_pallas and pcfg.image_size == cfg["image_size"]


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,params", [
    ("poisson", {"rate_hz": 500.0}),
    ("bursty", {"rate_hz": 500.0, "burst_sizes": [5, 6, 7],
                "jitter_s": 0.001}),
])
def test_open_loop_schedules_are_seeded(kind, params):
    mod = run.traffic_kind(kind)
    a = mod.schedule(params, np.random.default_rng(2**33 + 1), 10.0)
    b = mod.schedule(params, np.random.default_rng(2**33 + 1), 10.0)
    c = mod.schedule(params, np.random.default_rng(7), 10.0)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert len(a) == len(c) == 5000
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 10.0
    if kind == "poisson":               # the same gaps, in another order
        np.testing.assert_allclose(np.sort(np.diff(a)), np.sort(np.diff(c)),
                                   rtol=0, atol=1e-9 * 10 + 0.05 / 5000)


def test_poisson_gaps_are_exponential():
    mod = run.traffic_kind("poisson")
    due = mod.schedule({"rate_hz": 1000.0}, np.random.default_rng(3), 20.0)
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(1e-3, rel=0.01)
    assert np.median(gaps) == pytest.approx(np.log(2) * 1e-3, rel=0.02)


# ---------------------------------------------------------------------------
# runs
# ---------------------------------------------------------------------------
def test_run_without_a_chip_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "alexnet.closed",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def _tiny_cell(model="alexnet"):
    """The closed cell of ``model`` at CPU-test size, held to the limit of
    the full-size configuration."""
    with open(os.path.join(BENCH, "tests", "data", f"{model}_tiny.json")) as f:
        cfg = json.load(f)
    cfg["check"]["logit_err"] = _cfg(model)["check"]["logit_err"]
    closed = run.cell(f"{model}.closed")
    traffic = {"kind": "closed", "clients": 2 * cfg["max_batch"], "pool": 8,
               "buckets": [cfg["max_batch"]]}
    return run.Cell(closed.name, 1, cfg, traffic, closed.end_to_end,
                    closed.per_layer)


@pytest.mark.parametrize("model", ["alexnet", "vgg16"])
def test_sound_run_passes_and_control_fails(model):
    """The program passes ``logit_err``; the reference computed in three
    bfloat16 passes, put in its place, does not."""
    res = run.run_cell(_tiny_cell(model), 2**31 + 17, 2.0, False,
                       require_chip=False, controls=("bf16_3x",))
    limit = res["checks"]["logit_err"]["limit"]
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["controls"]["bf16_3x"] > limit, res
    assert list(res)[-1] == "checks"


def test_altered_answers_are_not_correct():
    """The forward's logits altered where they are produced (one logit of
    every row moved by a thousandth of the row's largest magnitude)."""
    import jax.numpy as jnp

    def alter(server):
        eng = server.engine
        fn = eng._fn

        def altered(*args):
            out = fn(*args)
            return out.at[:, 0].add(1e-3 * jnp.abs(out).max(axis=1))

        eng._fn = altered

    res = run.run_cell(_tiny_cell(), 5, 2.0, False, require_chip=False,
                       server_hook=alter)
    assert not res["correct"]
    assert (res["checks"]["logit_err"]["value"]
            > res["checks"]["logit_err"]["limit"])
