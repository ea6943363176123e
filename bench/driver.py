"""The two ways a traffic mix drives the server, on the host's clock.

* ``closed_loop``: a fixed number of clients, each with one request in
  flight and no think time; a slow server gets less load.
* ``open_loop``: requests due on a schedule that does not wait for the
  server.  Each request is timed from when it was due, so a stall that
  makes later requests late is counted, and the lateness of each submit
  is recorded.

Both run in one thread: the loop submits, then steps the engine.  Every
completion is observed by the harness after the step that retired it,
when its logits are on the host.  ``finish`` then serves what is still in
flight after the window's close, up to ``GRACE_S`` past it.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

# seconds past the window's close that a request sent in it may still take
GRACE_S = 60.0


@dataclass
class Record:
    """One request as the client saw it."""
    image: int                      # index into the image pool
    due: Optional[float]            # seconds after the window opened
    t_submit: float                 # harness clock
    req: object = None              # the program's request, until finished
    t_done: Optional[float] = None  # harness clock, when observed finished
    served: bool = False            # completed with finite logits


@dataclass
class Outcome:
    """What one window produced."""
    t_open: float
    t_close: float
    records: list = field(default_factory=list)     # every request sent
    inflight: list = field(default_factory=list)    # not finished at close
    lateness_s: list = field(default_factory=list)  # open loop: submit - due
    step_s: float = 0.0             # harness clock inside engine.step
    steps: int = 0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


class Sampler:
    """A uniform sample of ``k`` served requests, drawn from the seed
    (Algorithm R), keeping each one's image, logits and bucket."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.n = k, rng, 0
        self.items = []

    def offer(self, rec: Record):
        item = (rec.image, np.array(rec.req.logits, np.float32),
                rec.req.served_bucket)
        if len(self.items) < self.k:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.n + 1))
            if j < self.k:
                self.items[j] = item
        self.n += 1


def spans(enabled: bool):
    """``span(name)``: a profiler annotation when tracing, else nothing."""
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def _observe(inflight: list, t: float, sampler: Sampler) -> list:
    still = []
    for rec in inflight:
        r = rec.req
        if r.done or r.expired or r.shed:
            rec.t_done = t
            rec.served = bool(r.done and r.logits is not None
                              and np.isfinite(r.logits).all())
            if rec.served:
                sampler.offer(rec)
            rec.req = None          # its logits live on only in the sample
        else:
            still.append(rec)
    return still


def _step(server, span, out: Outcome):
    t = time.perf_counter()
    with span("bench.engine.step"):
        server.step()
    t_done = time.perf_counter()
    out.step_s += t_done - t
    out.steps += 1
    return t_done


def closed_loop(server, images, order, clients: int, seconds: float,
                sampler: Sampler, span) -> Outcome:
    """``clients`` clients with no think time send the images of the pool
    in ``order`` (cycled) for ``seconds``."""
    t0 = time.perf_counter()
    out = Outcome(t_open=t0, t_close=t0 + seconds)
    inflight, nxt = [], 0
    while time.perf_counter() < out.t_close:
        with span("bench.client.submit"):
            t = time.perf_counter()
            while len(inflight) < clients:
                rec = Record(image=int(order[nxt % len(order)]), due=None,
                             t_submit=t)
                rec.req = server.submit(images[rec.image])
                inflight.append(rec)
                out.records.append(rec)
                nxt += 1
        inflight = _observe(inflight, _step(server, span, out), sampler)
    out.inflight = inflight
    return out


def open_loop(server, images, order, due: np.ndarray, seconds: float,
              sampler: Sampler, span) -> Outcome:
    """Requests due at ``due`` (seconds after the window opens, sorted,
    all under ``seconds``), each with the next image of ``order``."""
    t0 = time.perf_counter()
    out = Outcome(t_open=t0, t_close=t0 + seconds)
    inflight, i, n = [], 0, len(due)
    while True:
        t = time.perf_counter()
        if t >= out.t_close:
            break
        if i < n and due[i] <= t - t0:
            with span("bench.client.submit"):
                while i < n and due[i] <= t - t0:
                    rec = Record(image=int(order[i % len(order)]),
                                 due=float(due[i]), t_submit=t)
                    rec.req = server.submit(images[rec.image])
                    out.lateness_s.append(t - t0 - due[i])
                    inflight.append(rec)
                    out.records.append(rec)
                    i += 1
        if server.idle():
            wait = (due[i] if i < n else seconds) - (time.perf_counter() - t0)
            with span("bench.client.wait"):
                time.sleep(max(0.0, min(wait, out.t_close - t)))
            continue
        inflight = _observe(inflight, _step(server, span, out), sampler)
    out.inflight = inflight
    return out


def finish(server, out: Outcome, sampler: Sampler, span):
    """Serve what was in flight at the window's close, up to ``GRACE_S``
    past it; these steps are not counted as the window's."""
    extra = Outcome(t_open=out.t_close, t_close=out.t_close)
    inflight = out.inflight
    while inflight and time.perf_counter() < out.t_close + GRACE_S:
        inflight = _observe(inflight, _step(server, span, extra), sampler)
    out.inflight = inflight
