"""Supervised multi-process serving: N worker processes, one referee.

The paper's deployment story (§4.3: host + accelerator board) has a
single failure domain — when the host serving process dies, the fleet
dies.  This module splits the serving tier into a parent-side
:class:`Supervisor` that owns N :mod:`~repro.serving.worker` processes
(each a full :class:`~repro.serving.registry.ModelRegistry` with its own
JAX runtime) and is the *sole* bookkeeper of the fleet invariant::

    submitted == completed + shed + expired        (after drain)

Requests are dispatched round-robin over *live* workers, where liveness
is the same :class:`~repro.serving.health.HealthMonitor` ladder the
engines use in-process, re-applied at process level: every pump sends a
heartbeat RPC; a miss (timeout) is a recorded failure, a reply is a
recorded ok, and a quarantined monitor means the worker is declared dead
— killed, respawned from its spec, and its work failed over.  A broken
pipe or a dead PID short-circuits the ladder via ``force_quarantine``.

Failover re-dispatch: the supervisor keeps every in-flight request's
pristine host image.  When a worker dies, its queued + in-flight
requests are re-submitted to survivors with their *remaining* deadline
(already-expired ones retire as expired, per the engine's own
accounting contract); nothing is ever silently lost, because a request
leaves the supervisor's in-flight table only through a retire record,
an expiry, or a shed — never through a worker death.

One process per chip: a TPU chip belongs to one process, so on a TPU host
the supervisor gives worker ``k`` chip ``k`` through its spec
(:func:`~repro.serving.worker.single_chip_env`, applied by the worker
before its first JAX call) and refuses more workers than the host has
chips.  It counts the chips' device nodes and never brings up a JAX
backend itself — the parity oracle runs in a worker.  On a CPU-only run
(``JAX_PLATFORMS`` without ``tpu``) workers start as plain processes.

Crash-consistent restart: a respawned worker rebuilds from its
:class:`~repro.serving.worker.WorkerSpec` — params from the newest
*intact* checkpoint (crc-verified, torn-latest falls back one step),
weight slabs repacked, the persisted autotuner plan cache reused — so a
replacement serves bit-identical logits to the process it replaced.
:meth:`Supervisor.verify_bit_parity` closes the loop: every failed-over
request's served logits must bit-match a jitted direct forward at the
exact padded bucket shape it was served in (rebuilt from the
``served_bucket/row/group`` provenance the engine stamps at retire),
recomputed by a live worker with ``init(seed)`` params.

Chaos is seeded per worker (``derive_seed(seed, worker_name)`` → one
:class:`~repro.serving.faults.FaultInjector` each): ``worker.crash``
SIGKILLs the process at a pump opportunity, ``worker.stall`` makes the
worker's command loop sleep so heartbeats miss without the process
dying — both bit-reproducible from (seed, specs).
"""
from __future__ import annotations

import glob
import multiprocessing as mp
import os
import socket
import time
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .clock import MONOTONIC, Clock
from .faults import FaultInjector, FaultSpec, derive_seed
from .health import QUARANTINED, HealthMonitor
from .scheduler import DrainTimeout, LatencyTracker
from .worker import WorkerModel, WorkerSpec, single_chip_env, worker_main

__all__ = ["Supervisor", "SupervisorConfig", "WorkerDead", "WorkerTimeout",
           "WorkerModel"]


class WorkerTimeout(RuntimeError):
    """An RPC to a worker exceeded its deadline (stall / overload) — a
    heartbeat miss, not yet a death."""


class WorkerDead(RuntimeError):
    """The worker's pipe is gone or its process exited — hard failure."""


@dataclass(frozen=True)
class SupervisorConfig:
    n_workers: int = 2
    heartbeat_timeout_ms: float = 1000.0   # miss if no reply within this
    miss_threshold: int = 3                # consecutive misses -> dead
    rpc_timeout_ms: float = 60_000.0       # submit/step/retire budget
    spawn_timeout_s: float = 600.0         # build + warmup compile budget
    steps_per_pump: int = 2                # registry ticks per step RPC
    max_restarts: int = 2                  # respawns per worker slot
    default_retries: int = 2               # engine-level retry budget
    warm: bool = True                      # compile buckets before 'ready'
    checkpoint_on_start: bool = True       # seed a checkpoint pre-crash


@dataclass
class _Handle:
    """Parent-side state for one worker slot (survives respawns)."""
    name: str
    spec: WorkerSpec
    proc: Optional[mp.Process] = None
    conn: object = None
    monitor: Optional[HealthMonitor] = None
    injector: Optional[FaultInjector] = None
    seq: int = 0
    pid: Optional[int] = None
    restarts: int = 0
    alive: bool = False                 # ready and believed serving
    spawning: bool = False              # process launched, ready pending
    t_spawn: float = 0.0                # launch time (spawn_timeout clock)
    retired: bool = False               # restart budget exhausted
    restored: dict = field(default_factory=dict)   # model -> ckpt step
    last_accounting: dict = field(default_factory=dict)
    deaths: List[str] = field(default_factory=list)
    # uid -> (model, supervisor-side ImageRequest record)
    inflight: Dict[int, Tuple[str, object]] = field(default_factory=dict)


def _src_root() -> str:
    # .../src/repro/serving/supervisor.py -> .../src
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def tpu_chip_count() -> Optional[int]:
    """TPU chips this process may open, or None where JAX will not use a
    TPU (a CPU-only run: ``JAX_PLATFORMS`` set without ``tpu``).

    Counted as the chips' device nodes (``/dev/accel<n>``, or one VFIO
    group ``/dev/vfio/<n>`` per chip), so asking brings up no backend and
    holds no chip.  The PCI bus would over-count in a container that is
    given only some of its host's chips."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return None
    nodes = glob.glob("/dev/accel[0-9]*") + [
        p for p in glob.glob("/dev/vfio/*") if os.path.basename(p).isdigit()]
    return len(nodes) or None


def _free_port() -> int:
    with socket.socket() as sk:
        sk.bind(("localhost", 0))
        return sk.getsockname()[1]


class Supervisor:
    """Own N worker processes; route, heartbeat, fail over, account."""

    def __init__(self, models: Sequence[WorkerModel],
                 sup: Optional[SupervisorConfig] = None, *,
                 ckpt_dir: Optional[str] = None,
                 seed: int = 0,
                 chaos: Optional[Dict[str, FaultSpec]] = None,
                 chaos_workers: Optional[Sequence[str]] = None,
                 clock: Optional[Clock] = None):
        self.models = tuple(models)
        self.sup = sup or SupervisorConfig()
        self.ckpt_dir = ckpt_dir
        self.seed = seed
        self.chaos = dict(chaos or {})
        self.clock = clock or MONOTONIC
        self._ctx = mp.get_context("spawn")
        # spawn children re-import repro to unpickle the spec; make sure
        # they can even when the parent added src/ to sys.path manually
        root = _src_root()
        pp = os.environ.get("PYTHONPATH", "")
        if root not in pp.split(os.pathsep):
            os.environ["PYTHONPATH"] = (root + os.pathsep + pp) if pp else root

        # one chip per worker on a TPU host (respawns reuse the slot's)
        chips = tpu_chip_count()
        if chips is not None and self.sup.n_workers > chips:
            raise ValueError(f"{self.sup.n_workers} workers but only "
                             f"{chips} TPU chips: a chip serves one "
                             f"process")
        self.workers: Dict[str, _Handle] = {}
        for k in range(self.sup.n_workers):
            name = f"w{k}"
            spec = WorkerSpec(name=name, models=self.models,
                              ckpt_dir=ckpt_dir, warm=self.sup.warm)
            if chips is not None:
                spec = replace(spec, env=tuple(
                    single_chip_env(k, _free_port()).items()))
            # chaos_workers narrows the blast radius: "kill worker k at
            # opportunity s" schedules (FaultSpec(at=...)) would otherwise
            # fire on every worker at the same pump index
            armed = self.chaos and (chaos_workers is None
                                    or name in chaos_workers)
            inj = (FaultInjector(derive_seed(seed, name), self.chaos)
                   if armed else None)
            self.workers[name] = _Handle(name=name, spec=spec, injector=inj)

        # fleet accounting — the supervisor's counters are authoritative;
        # worker-side counters are diagnostics (heartbeat snapshots)
        self.submitted = 0
        self.completed = 0
        self.shed = 0
        self.expired = 0
        self.failed_over = 0
        self.latency = LatencyTracker()
        self.requests: Dict[int, Tuple[str, object]] = {}  # uid -> (model, req)
        self.pending: List[Tuple[str, object]] = []  # parked during outage
        self.failover_uids: set = set()
        self.events: List[dict] = []
        self._rr = 0                    # round-robin cursor
        self._started = False

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "Supervisor":
        # launch every worker first, then wait: the N builds (JAX import +
        # bucket warmup compiles) run in parallel instead of serially
        for h in self.workers.values():
            self._launch_proc(h)
        for h in self.workers.values():
            if not self._finalize_ready(h, block=True):
                raise WorkerDead(f"{h.name}: failed to come up "
                                 f"({h.deaths[-1] if h.deaths else '?'})")
        if self.ckpt_dir and self.sup.checkpoint_on_start:
            self.checkpoint()
        self._started = True
        return self

    def __enter__(self) -> "Supervisor":
        return self.start() if not self._started else self

    def __exit__(self, *exc):
        self.shutdown()

    def _fresh_monitor(self) -> HealthMonitor:
        # process-level reuse of the engine health ladder: misses walk
        # healthy -> degraded -> quarantined; quarantined == declared dead
        return HealthMonitor(
            fail_threshold=max(1, self.sup.miss_threshold - 1),
            quarantine_threshold=self.sup.miss_threshold)

    def _launch_proc(self, h: _Handle):
        """Start the worker process without waiting for its ready
        handshake — builds (JAX import, warmup compiles) take tens of
        seconds, and a blocked supervisor would stall the whole fleet's
        heartbeats and deadlines (the respawn path pumps survivors while
        the replacement comes up)."""
        parent, child = self._ctx.Pipe()
        proc = self._ctx.Process(target=worker_main, args=(child, h.spec),
                                 daemon=True, name=f"serve-{h.name}")
        proc.start()
        child.close()
        h.proc, h.conn = proc, parent
        h.seq = 0
        h.alive, h.spawning = False, True
        h.t_spawn = time.monotonic()

    def _finalize_ready(self, h: _Handle, *, block: bool) -> bool:
        """Absorb the ready handshake.  ``block=False`` (pump path) polls
        and returns False while the build is still running; a build
        failure or spawn timeout retires the attempt (counted against the
        restart budget by the caller's next death handling)."""
        try:
            if not h.conn.poll(self.sup.spawn_timeout_s if block else 0):
                if (block or time.monotonic() - h.t_spawn
                        > self.sup.spawn_timeout_s):
                    self._spawn_failed(h, "no ready handshake within "
                                       f"{self.sup.spawn_timeout_s}s")
                return False
            ready = h.conn.recv()
        except (EOFError, OSError) as e:
            self._spawn_failed(h, f"{type(e).__name__}: {e}")
            return False
        if not ready.get("ok"):
            self._spawn_failed(h, f"build failed: "
                               f"{ready.get('error', 'unknown')}")
            return False
        h.pid = ready.get("pid")
        h.monitor = self._fresh_monitor()
        h.alive, h.spawning = True, False
        h.restored = dict(ready.get("restored") or {})
        self.events.append({"event": "spawn", "worker": h.name,
                            "pid": h.pid, "restarts": h.restarts,
                            "restored": h.restored})
        return True

    def _spawn_failed(self, h: _Handle, reason: str):
        h.spawning = False
        h.deaths.append(f"spawn-failed: {reason}")
        self.events.append({"event": "spawn-failed", "worker": h.name,
                            "reason": reason})
        if h.proc is not None:
            h.proc.kill()
            h.proc.join(timeout=10)
        if h.conn is not None:
            h.conn.close()
            h.conn = None
        if h.restarts < self.sup.max_restarts:
            h.restarts += 1
            self._launch_proc(h)
        else:
            h.retired = True
            self.events.append({"event": "retired", "worker": h.name})

    def shutdown(self):
        for h in self.workers.values():
            if h.conn is not None and h.alive:
                try:
                    self._rpc(h, {"op": "shutdown"}, timeout_s=5.0)
                except (WorkerDead, WorkerTimeout):
                    pass
            if h.proc is not None:
                h.proc.join(timeout=5)
                if h.proc.is_alive():
                    h.proc.kill()
                    h.proc.join(timeout=5)
            if h.conn is not None:
                h.conn.close()
            h.alive = False

    # -- RPC ----------------------------------------------------------------
    def _rpc(self, h: _Handle, msg: dict, timeout_s: float) -> dict:
        """Seq-matched request/reply with deadline.  Replies to RPCs that
        already timed out (a recovered stall) are recognised by their
        stale seq and dropped — never matched to the wrong call."""
        h.seq += 1
        msg = dict(msg, seq=h.seq)
        try:
            h.conn.send(msg)
            deadline = time.monotonic() + timeout_s
            while True:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not h.conn.poll(max(remaining, 0.0)):
                    raise WorkerTimeout(
                        f"{h.name}: no reply to {msg['op']!r} within "
                        f"{timeout_s * 1e3:.0f}ms")
                reply = h.conn.recv()
                if reply.get("seq") == h.seq:
                    return reply
        except (EOFError, BrokenPipeError, ConnectionResetError,
                OSError) as e:
            raise WorkerDead(
                f"{h.name}: {type(e).__name__}: {e}") from e

    def _send_only(self, h: _Handle, msg: dict):
        """Fire-and-forget (chaos stall payload); the eventual reply is
        dropped by seq matching."""
        h.seq += 1
        try:
            h.conn.send(dict(msg, seq=h.seq))
        except (BrokenPipeError, OSError):
            pass

    # -- routing + submit ---------------------------------------------------
    def _live(self) -> List[_Handle]:
        return [h for h in self.workers.values()
                if h.alive and h.monitor is not None
                and h.monitor.state != QUARANTINED
                and h.proc is not None and h.proc.is_alive()]

    def _route(self, exclude: set) -> Optional[_Handle]:
        live = [h for h in self._live() if h.name not in exclude]
        if not live:
            return None
        h = live[self._rr % len(live)]
        self._rr += 1
        return h

    def submit(self, model: str, req) -> bool:
        """Dispatch one request to a live worker.  Returns False (and
        counts a shed) when every live worker refuses or none exists."""
        req.t_submit = self.clock.now()
        self.submitted += 1
        self.requests[req.uid] = (model, req)
        return self._dispatch(model, req, first=True)

    def _remaining_deadline_ms(self, req, now: float) -> Optional[float]:
        if req.deadline_ms is None:
            return None
        return req.deadline_ms - (now - req.t_submit) * 1e3

    def _dispatch(self, model: str, req, *, first: bool) -> bool:
        tried: set = set()
        while True:
            h = self._route(tried)
            if h is None:
                req.shed = True
                self.shed += 1
                return False
            remaining = self._remaining_deadline_ms(req, self.clock.now())
            if remaining is not None and remaining <= 0:
                self._expire(req, "deadline")
                return False
            try:
                rep = self._rpc(h, {"op": "submit", "model": model,
                                    "uid": req.uid, "image": req.image,
                                    "deadline_ms": remaining,
                                    "retries": req.retries},
                                timeout_s=self.sup.rpc_timeout_ms / 1e3)
            except WorkerDead as e:
                self._on_worker_death(h, str(e))
                tried.add(h.name)
                continue
            except WorkerTimeout:
                h.monitor.record_failure("submit-timeout")
                tried.add(h.name)
                continue
            if rep.get("accepted"):
                h.inflight[req.uid] = (model, req)
                if not first:
                    self.failed_over += 1
                    self.failover_uids.add(req.uid)
                return True
            tried.add(h.name)       # shed at this worker; try another

    def _expire(self, req, reason: str):
        req.expired = True
        req.expire_reason = reason
        self.expired += 1

    # -- death + failover ---------------------------------------------------
    def kill_worker(self, name: str, reason: str = "operator-kill"):
        """SIGKILL a worker (chaos / drills) and run the failover path."""
        h = self.workers[name]
        if h.proc is not None and h.proc.is_alive():
            h.proc.kill()
        self._on_worker_death(h, reason)

    def _on_worker_death(self, h: _Handle, reason: str):
        if not h.alive:
            return                          # already handled (re-entrant)
        h.alive = False
        h.deaths.append(reason)
        if h.monitor is not None and h.monitor.state != QUARANTINED:
            h.monitor.force_quarantine(reason)
        self.events.append({"event": "death", "worker": h.name,
                            "pid": h.pid, "reason": reason})
        if h.proc is not None:
            h.proc.kill()
            h.proc.join(timeout=10)
        if h.conn is not None:
            h.conn.close()
            h.conn = None
        orphans = list(h.inflight.values())
        h.inflight.clear()
        # failover re-dispatch FIRST, to survivors, at the remaining
        # deadline — the respawn takes tens of seconds (JAX import +
        # warmup) and must never gate the orphans' deadlines
        now = self.clock.now()
        for model, req in orphans:
            remaining = self._remaining_deadline_ms(req, now)
            if remaining is not None and remaining <= 0:
                self._expire(req, "deadline")
            elif self._live():
                self._dispatch(model, req, first=False)
            else:
                # total outage: park until a worker comes back (drained
                # stays False; the pump re-dispatches on recovery)
                self.pending.append((model, req))
        # crash-consistent restart, asynchronously: same spec ->
        # checkpoint-restored params, repacked slabs, reused plan cache;
        # the ready handshake is absorbed by a later pump
        if h.restarts < self.sup.max_restarts:
            h.restarts += 1
            self._launch_proc(h)
        else:
            h.retired = True
            self.events.append({"event": "retired", "worker": h.name})

    # -- pump ---------------------------------------------------------------
    def step(self):
        """One supervisory tick over every worker slot: respawn
        handshakes, chaos, liveness, heartbeat, registry steps,
        retirement, and re-dispatch of outage-parked requests."""
        for h in list(self.workers.values()):
            if h.spawning:
                self._finalize_ready(h, block=False)
            if h.retired or not h.alive:
                continue
            if h.injector is not None:
                if h.injector.fire("worker.crash"):
                    self.kill_worker(h.name, "chaos:worker.crash")
                    continue
                spec = h.injector.fire("worker.stall")
                if spec is not None and spec.delay_ms:
                    self._send_only(h, {"op": "stall",
                                        "delay_ms": spec.delay_ms})
            if h.proc is None or not h.proc.is_alive():
                self._on_worker_death(h, "process-exit")
                continue
            try:
                rep = self._rpc(h, {"op": "heartbeat"},
                                timeout_s=self.sup.heartbeat_timeout_ms / 1e3)
                h.monitor.record_ok()
                h.last_accounting = rep.get("accounting", {})
            except WorkerTimeout:
                h.monitor.record_failure("heartbeat-miss")
                if h.monitor.state == QUARANTINED:
                    self.kill_worker(h.name, "heartbeat-quarantine")
                continue
            except WorkerDead as e:
                self._on_worker_death(h, str(e))
                continue
            try:
                self._rpc(h, {"op": "step", "n": self.sup.steps_per_pump},
                          timeout_s=self.sup.rpc_timeout_ms / 1e3)
                rep = self._rpc(h, {"op": "retire_batch"},
                                timeout_s=self.sup.rpc_timeout_ms / 1e3)
            except WorkerTimeout:
                h.monitor.record_failure("rpc-timeout")
                if h.monitor.state == QUARANTINED:
                    self.kill_worker(h.name, "rpc-quarantine")
                continue
            except WorkerDead as e:
                self._on_worker_death(h, str(e))
                continue
            self._absorb_retirements(h, rep.get("results", []))
        if self.pending:
            if self._live():
                parked, self.pending = self.pending, []
                now = self.clock.now()
                for model, req in parked:
                    remaining = self._remaining_deadline_ms(req, now)
                    if remaining is not None and remaining <= 0:
                        self._expire(req, "deadline")
                    else:
                        self._dispatch(model, req, first=False)
            elif all(h.retired for h in self.workers.values()):
                # permanent outage: no capacity will ever return — shed
                # (reported, accounted) instead of hanging the drain
                parked, self.pending = self.pending, []
                for _model, req in parked:
                    req.shed = True
                    self.shed += 1

    def _absorb_retirements(self, h: _Handle, results: List[dict]):
        now = self.clock.now()
        for rec in results:
            ent = h.inflight.pop(rec["uid"], None)
            if ent is None:
                continue        # stale: request was failed over elsewhere
            model, req = ent
            if rec["status"] == "done":
                req.logits = rec["logits"]
                req.label = rec["label"]
                req.served_bucket = rec["bucket"]
                req.served_row = rec["row"]
                req.served_group = rec["group"]
                req.attempts = rec.get("attempts", req.attempts)
                req.done = True
                req.t_done = now
                self.completed += 1
                self.latency.record(now - req.t_submit)
            else:
                req.expire_reason = rec.get("expire_reason")
                req.expired = True
                self.expired += 1

    # -- drain + accounting -------------------------------------------------
    @property
    def in_flight(self) -> int:
        return (sum(len(h.inflight) for h in self.workers.values())
                + len(self.pending))

    @property
    def drained(self) -> bool:
        return self.in_flight == 0

    def run_until_done(self, max_steps: int = 10_000) -> dict:
        for _ in range(max_steps):
            if self.drained:
                return self.accounting()
            self.step()
            if not self._live() and not all(
                    h.retired for h in self.workers.values()):
                # total outage with respawns in flight: pumping costs
                # nothing (no RPCs), so back off instead of burning the
                # step budget before any replacement can finish its build
                time.sleep(0.05)
        if self.drained:
            return self.accounting()
        raise DrainTimeout(
            f"fleet not drained after {max_steps} supervisor steps: "
            f"{self.accounting()}", self.accounting())

    def accounting(self) -> dict:
        """The fleet invariant, from the supervisor's own authoritative
        counters: no worker death may lose a request."""
        acc = {
            "submitted": self.submitted,
            "completed": self.completed,
            "shed": self.shed,
            "expired": self.expired,
            "in_flight": self.in_flight,
            "failed_over": self.failed_over,
        }
        acc["balanced"] = (self.submitted == self.completed + self.shed
                           + self.expired + self.in_flight)
        return acc

    def checkpoint(self) -> dict:
        """Persist every model's params via one live worker (they share
        seed-derived params, so one snapshot covers the fleet)."""
        live = self._live()
        if not live:
            raise WorkerDead("no live worker to checkpoint")
        return self._rpc(live[0], {"op": "checkpoint"},
                         timeout_s=self.sup.rpc_timeout_ms / 1e3)

    def stats(self) -> dict:
        per = {}
        for h in self.workers.values():
            per[h.name] = {
                "alive": h.alive,
                "retired": h.retired,
                "pid": h.pid,
                "restarts": h.restarts,
                "deaths": list(h.deaths),
                "restored": h.restored,
                "inflight": len(h.inflight),
                "health": h.monitor.stats() if h.monitor else None,
                "chaos": h.injector.summary() if h.injector else None,
                "accounting": h.last_accounting,
            }
        return {"accounting": self.accounting(), "workers": per,
                "events": list(self.events),
                "latency": self.latency.percentiles_ms()}

    # -- failover bit-parity ------------------------------------------------
    def verify_bit_parity(self, *, uids: Optional[Sequence[int]] = None
                          ) -> dict:
        """Check served logits against a jitted direct forward at the
        exact padded bucket shape each request was served in (rebuilt
        from the retire-time provenance), recomputed by a live worker with
        ``init(seed)`` params — what an un-checkpointed worker serves, and
        independent of any checkpoint a worker restored.  This process
        never runs JAX.  Defaults to every completed *failed-over* request
        — the failover contract.
        """
        if uids is None:
            uids = [u for u in sorted(self.failover_uids)
                    if self.requests[u][1].done]
        cfg_of = {m.name: m.cfg for m in self.models}
        checked = mismatched = 0
        bad: List[int] = []
        for uid in uids:
            model, req = self.requests[uid]
            if not req.done or req.served_bucket is None:
                continue
            cfg = cfg_of[model]
            buf = np.zeros((req.served_bucket, cfg.image_size,
                            cfg.image_size, cfg.in_channels),
                           np.dtype(getattr(cfg, "dtype", "float32")))
            for i, guid in enumerate(req.served_group):
                buf[i] = self.requests[guid][1].image
            ref = self._reference(model, buf)
            checked += 1
            if not np.array_equal(ref[req.served_row],
                                  np.asarray(req.logits)):
                mismatched += 1
                bad.append(uid)
        return {"checked": checked, "mismatched": mismatched,
                "bad_uids": bad}

    def _reference(self, model: str, images: np.ndarray) -> np.ndarray:
        """The parity oracle's logits for one padded batch, from the first
        live worker that answers."""
        for h in self._live():
            try:
                rep = self._rpc(h, {"op": "reference", "model": model,
                                    "images": images},
                                timeout_s=self.sup.spawn_timeout_s)
            except WorkerDead as e:
                self._on_worker_death(h, str(e))
                continue
            except WorkerTimeout:
                h.monitor.record_failure("rpc-timeout")
                continue
            return rep["logits"]
        raise WorkerDead("no live worker to recompute the parity oracle")
