"""CPU tests of the reduction of the program's ``cnn.*`` spans
(``cnn_spans.py``) and of the metric readers that use it.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import sys
from types import SimpleNamespace

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import cnn_spans  # noqa: E402
import run  # noqa: E402
import trace as tr  # noqa: E402

PHASE_METRICS = [f"engine.{p}_ms_per_batch"
                 for p in ("stage", "put", "launch", "fetch", "retire")]
NEW_METRICS = PHASE_METRICS + ["engine.queue_wait_ms"]

# Window [0, 100) ns.  Step 1 [10, 60): stage [12, 30) holding put b0
# [15, 25) and put b1 [26, 28), launch b0 [30, 35), fetch b0 [35, 50),
# retire [50, 58); device ops [20, 22) and [36, 48).  Step 2 [70, 120),
# past the window's close: launch b1 [72, 110) holding compile [73, 105),
# fetch b1 [110, 118).  A harness span wraps each step exactly.
_EVENTS = [  # (metadata id, start ns, duration ns, stats)
    (1, 0, 100, ""),
    (2, 10, 50, ""), (3, 10, 50, ""),
    (4, 12, 18, ""),
    (5, 15, 10, 'stats { metadata_id: 1 int64_value: 0 } '
                'stats { metadata_id: 3 str_value: "100 300 200" }'),
    (5, 26, 2, 'stats { metadata_id: 1 int64_value: 1 } '
               'stats { metadata_id: 3 str_value: "900" }'),
    (6, 30, 5, 'stats { metadata_id: 1 int64_value: 0 } '
               'stats { metadata_id: 2 int64_value: 4 }'),
    (7, 35, 15, 'stats { metadata_id: 1 int64_value: 0 }'),
    (8, 50, 8, ""),
    (2, 70, 50, ""), (3, 70, 50, ""),
    (6, 72, 38, 'stats { metadata_id: 1 int64_value: 1 } '
                'stats { metadata_id: 2 int64_value: 2 }'),
    (9, 73, 32, 'stats { metadata_id: 2 int64_value: 2 }'),
    (7, 110, 8, 'stats { metadata_id: 1 int64_value: 1 }'),
]
_NAMES = ["bench.window", "bench.engine.step", "cnn.step", "cnn.stage",
          "cnn.put", "cnn.launch", "cnn.fetch", "cnn.retire", "cnn.compile"]


def _xspace(events, names, ops=((20, 2), (36, 12))):
    host = "\n".join(
        f"    events {{ metadata_id: {i} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} {st} }}" for i, s, d, st in events)
    meta = "\n".join(f'  event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names, 1))
    dev = "\n".join(f"    events {{ metadata_id: 1 offset_ps: {s * 1000} "
                    f"duration_ps: {d * 1000} }}" for s, d in ops)
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{dev} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
{host} }}
{meta}
  stat_metadata {{ key: 1 value {{ id: 1 name: "batch" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "bucket" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "queue_wait_us" }} }} }}
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_innermost_pieces():
    spans = [("a", 0, 10), ("b", 2, 4), ("c", 6, 8), ("d", 12, 14),
             ("e", 13, 20)]          # e outlasts its parent d: cut at 14
    assert cnn_spans.innermost(spans) == [
        ("a", 0, 2), ("b", 2, 4), ("a", 4, 6), ("c", 6, 8), ("a", 8, 10),
        ("d", 12, 13), ("e", 13, 14)]


def test_idle_goes_to_the_innermost_span():
    prof = _profile(_xspace(_EVENTS, _NAMES))
    sp = cnn_spans.reduce(prof, chips=1)
    assert sp.found
    assert sp.idle_s == pytest.approx(
        {"step": 6e-9, "stage": 6e-9, "put": 10e-9, "launch": 6e-9,
         "fetch": 3e-9, "retire": 8e-9, "compile": 27e-9}, abs=1e-15)
    assert set(sp.idle_s) == set(cnn_spans.PHASES)
    # the phases and the step's self time are the idle time the harness
    # finds under its own span around each step
    r = tr.reduce(*tr.load(prof, 1), set())
    assert sum(sp.idle_s.values()) == pytest.approx(r.idle_in_step_s,
                                                    rel=1e-12)
    # batch 1 is fetched after the window's close: its wait is not read
    assert sorted(sp.queue_waits_ms) == pytest.approx([0.1, 0.2, 0.3])


def test_idle_is_averaged_over_chips():
    prof = _profile(_xspace(_EVENTS, _NAMES))
    device, _ = tr.load(prof, 1)
    lines = cnn_spans.load(prof)
    one = cnn_spans.idle_by_phase(device, (0, 100), lines)
    two = cnn_spans.idle_by_phase(device + [[]], (0, 100), lines)
    assert two["fetch"] == pytest.approx((one["fetch"] + 15e-9) / 2)


def test_chip_slice_without_program_spans_reads_empty():
    """The v5e slice predates the program's spans, so they read nothing
    there (``test_bench.py`` holds the harness's reduction of it)."""
    with open(os.path.join(BENCH, "testdata", "v5e_alexnet_slice.json")) as f:
        sl = json.load(f)
    lo, hi = sl["window"]
    device = [[(n, s - lo, e - lo) for n, s, e in sl["device"]]]
    assert not any(n.startswith(cnn_spans.PREFIX) for n, _, _ in sl["host"])
    assert cnn_spans.idle_by_phase(device, (0, hi - lo), []) == {}
    assert not cnn_spans.Spans().found


def _measured(tmp_path, monkeypatch, text, batches):
    """A traced window written where ``run.py`` writes it, and what the
    metric readers are given for it."""
    from jax.profiler import ProfileData

    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(cnn_spans, "TRACE_DIR", str(tmp_path))
    red = tr.reduce(*tr.load(_profile(text), 1), set())
    return SimpleNamespace(chips=1, trace=red, batches=batches)


def test_readers_split_the_engines_idle_time(tmp_path, monkeypatch):
    m = _measured(tmp_path, monkeypatch, _xspace(_EVENTS, _NAMES), {4: 1})
    got = {n: run.metric_reader(n).read(m) for n in NEW_METRICS}
    assert got == pytest.approx(
        {"engine.stage_ms_per_batch": 6e-6, "engine.put_ms_per_batch": 10e-6,
         "engine.launch_ms_per_batch": 6e-6,
         "engine.fetch_ms_per_batch": 3e-6,
         "engine.retire_ms_per_batch": 8e-6, "engine.queue_wait_ms": 0.2},
        abs=1e-12)
    host = run.metric_reader("engine.host_ms_per_batch.closed").read(m)
    assert sum(got[n] for n in PHASE_METRICS) <= host


def test_readers_report_nothing_without_program_spans(tmp_path,
                                                      monkeypatch):
    """A program without the spans (a trace with the harness's alone) and
    an untraced run: every new reader returns None and raises nothing."""
    harness = [e for e in _EVENTS if _NAMES[e[0] - 1].startswith("bench.")]
    m = _measured(tmp_path, monkeypatch, _xspace(harness, _NAMES), {4: 1})
    assert m.trace.idle_in_step_s > 0
    for n in NEW_METRICS:
        assert run.metric_reader(n).read(m) is None
        assert run.metric_reader(n).read(
            SimpleNamespace(chips=1, trace=None, batches={4: 1})) is None


def test_new_metrics_are_declared_for_the_closed_cells():
    per_layer = {m["name"]: m for m in run.benchmark()["per_layer"]}
    for n in NEW_METRICS:
        m = per_layer[n]
        assert m["moves"] == "img_per_s" and m["unit"] == "ms"
        assert m["layer"] == per_layer["engine.host_ms_per_batch.closed"][
            "layer"]
        assert m["workloads"] == ["alexnet.closed", "vgg16.closed"]
