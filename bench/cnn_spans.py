"""The program's own spans in a traced window: where the engine's
device-idle time goes.

``CnnEngine`` (``src/repro/serving/cnn.py``) marks each ``step()`` with
``cnn.*`` profiler spans on the profiler's clock: ``cnn.step`` holds
``cnn.stage`` (admission and the host buffer, holding one ``cnn.put`` per
group for the H2D ``device_put``), ``cnn.launch``, ``cnn.fetch`` and
``cnn.retire``; ``cnn.compile`` wraps a bucket's compile.  ``trace.load``
keeps only the harness's ``bench.*`` spans; this module reads the
``cnn.*`` ones, with their stats:

* ``idle_by_phase``: each device-idle stretch inside ``bench.window`` goes
  to the innermost ``cnn.*`` span over it, so ``stage`` and ``step`` are
  self time (``stage`` without its ``put``); idle under no ``cnn.*`` span
  is not counted;
* ``queue_waits_ms``: the queue wait of each request of a batch whose
  ``cnn.fetch`` ends in the window, from the ``queue_wait_us`` stat of the
  batch's ``cnn.put`` (the engine's ``t_admit - t_submit``).

The layers' scopes (``conv1``, ``fc6``) cannot be read here: a TPU
trace's op events carry no scope path, only the kernels' own names.

A trace of a program without these spans reads empty, and the metrics
that read it report nothing.
"""
from __future__ import annotations

import json
import os
import sys
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

import trace as tr

PREFIX = "cnn."
PHASES = ("stage", "put", "launch", "fetch", "retire", "compile", "step")
# where ``run.py`` writes the traced window
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "out", "trace")


@dataclass
class Spans:
    """One traced window, as the program's spans split it."""
    idle_s: dict = field(default_factory=dict)   # phase -> idle s, mean/chip
    queue_waits_ms: list = field(default_factory=list)

    @property
    def found(self) -> bool:
        return "step" in self.idle_s


def load(profile):
    """The host events named ``cnn.*``, each ``(name, start_ns, end_ns,
    stats)``, grouped by the host line (thread) they are on."""
    lines = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats))
                  for e in line.events if e.name.startswith(PREFIX)]
            if ev:
                lines.append(ev)
    return lines


def innermost(spans):
    """Disjoint ``(name, start, end)`` pieces of nested spans (as on one
    thread), each instant under the innermost span over it.  A span that
    outlasts its parent is cut at the parent's end."""
    out, stack, t = [], [], None
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            if end > t:
                out.append((n, t, end))
                t = end
        if stack:
            if s > t:
                out.append((stack[-1][0], t, s))
            e = min(e, stack[-1][1])
        stack.append((name, e))
        t = s
    while stack:
        n, end = stack.pop()
        if end > t:
            out.append((n, t, end))
            t = end
    return out


def idle_by_phase(device: list, window, lines) -> dict:
    """Idle seconds inside ``window`` (ns) under each innermost ``cnn.*``
    span, mean over the chips in ``device`` (as ``trace.load`` gives it)."""
    lo, hi = window
    pieces = defaultdict(list)
    for ev in lines:
        for n, s, e in innermost([(n, s, e) for n, s, e, _ in ev]):
            pieces[n.removeprefix(PREFIX)].append((s, e))
    pieces = {n: tr._union(tr._clip(np.array(v, float), lo, hi))
              for n, v in pieces.items()}
    idle = dict.fromkeys(pieces, 0.0)
    for chip in device:
        iv = np.array([(s, e) for _, s, e in chip], float).reshape(-1, 2)
        gaps = tr._gaps(tr._union(tr._clip(iv, lo, hi)), lo, hi)
        for n, p in pieces.items():
            idle[n] += tr._overlap(gaps, p)
    k = max(len(device), 1)
    return {n: t * 1e-9 / k for n, t in idle.items()}


def queue_waits_ms(lines, window) -> list:
    """Queue waits (ms) of the requests of each batch whose ``cnn.fetch``
    ends inside ``window``."""
    lo, hi = window
    fetched, waits = set(), []
    for ev in lines:
        fetched.update(st.get("batch") for n, _, e, st in ev
                       if n == "cnn.fetch" and lo <= e <= hi)
    for ev in lines:
        for n, _, _, st in ev:
            if n == "cnn.put" and st.get("batch") in fetched:
                waits += [int(w) / 1e3
                          for w in str(st.get("queue_wait_us", "")).split()]
    return waits


def reduce(profile, chips: int) -> Spans:
    """The program's spans of a ``jax.profiler.ProfileData`` trace, over
    the first ``chips`` chips."""
    device, host = tr.load(profile, chips)
    windows = [(s, e) for n, s, e in host if n == tr.WINDOW]
    lines = load(profile)
    if len(windows) != 1 or not lines:
        return Spans()
    w = windows[0]
    return Spans(idle_by_phase(device, w, lines), queue_waits_ms(lines, w))


def reading(m) -> Spans:
    """The spans of the window that ``run.py`` traced for the metrics
    ``m`` (``run.Measured``), reduced once and kept on ``m``; empty where
    the run was not traced.  The reduction prints one ``spans:`` line on
    standard error."""
    if m.trace is None:
        return Spans()
    sp = vars(m).get("cnn_spans")
    if sp is None:
        from jax.profiler import ProfileData

        sp = reduce(ProfileData.from_file(tr.xplane_path(TRACE_DIR)),
                    m.chips)
        m.cnn_spans = sp
        w = sp.queue_waits_ms
        print(f"spans: device-idle s by innermost program span "
              f"{json.dumps(sp.idle_s)}; queue wait p50 "
              f"{float(np.median(w)) if w else None} ms over {len(w)} "
              f"requests", file=sys.stderr)
    return sp


def per_batch_ms(m, phase: str):
    """Device-idle ms under ``cnn.<phase>`` per batch retired in the
    window; None where the program has no such spans."""
    batches = sum(m.batches.values())
    sp = reading(m)
    if not sp.found or not batches:
        return None
    return sp.idle_s.get(phase, 0.0) / batches * 1e3
