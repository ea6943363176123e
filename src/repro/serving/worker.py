"""Serving worker process: a :class:`ModelRegistry` behind a pickled pipe.

PipeCNN (PAPERS.md) decouples its data-mover and compute kernels into
independent concurrently-running units; the fleet-scale analogue is
decoupling the *serving host* itself: each worker is a separate OS process
owning its own JAX runtime, compiled buckets, packed weight slabs, and
:class:`~repro.serving.registry.ModelRegistry`, so one worker's crash,
stall, or leak cannot take down the rest of the fleet.  The parent-side
:class:`~repro.serving.supervisor.Supervisor` owns N of these and speaks
the small request/reply protocol below over a duplex
``multiprocessing.Pipe`` (messages are plain dicts + numpy arrays —
pickle-over-pipe, nothing fancier).

Protocol (every request carries a ``seq`` the reply echoes, so a reply
that arrives after its RPC timed out — a recovered stall — is recognised
and dropped instead of being matched to the wrong call):

==================  ======================================================
``submit``          enqueue one request ``{model, uid, image, deadline_ms,
                    retries}`` through the engine's admission control;
                    reply ``{accepted}`` (False = shed at the worker)
``step``            tick the registry ``n`` times (stage -> launch ->
                    retire overlap inside each engine); reply ``{drained}``
``retire_batch``    pop every finished request; reply ``{results: [...]}``
                    — per request: uid, status (``done``/``expired``),
                    logits/label, expire_reason, and the serving
                    provenance (``bucket``/``row``/``group``) a failover
                    verifier needs to rebuild the exact padded batch
``heartbeat``       liveness probe; reply carries queue depth + the
                    per-model accounting snapshot
``checkpoint``      persist every model's params (per-file crc32 manifest,
                    atomic publish) under ``<ckpt_dir>/<model>/``; reply
                    ``{paths}``
``reference``       the parity oracle: a jitted direct forward of ``model``
                    with ``init(seed)`` params — independent of any
                    checkpoint this worker restored — on the padded batch
                    ``images``; reply ``{logits}``
``stall``           chaos payload (``worker.stall``): sleep ``delay_ms``
                    before replying, so the supervisor's heartbeat
                    deadline trips without the process dying
``shutdown``        ack, close the pipe, exit 0
==================  ======================================================

Crash-consistent restart: at build, each model's params come from the
newest *intact* checkpoint under ``<ckpt_dir>/<model>/`` when one exists
(:func:`repro.checkpoint.restore` verifies the crc manifest and falls
back past a torn latest step), else from ``init(seed)`` — either way the
respawned worker repacks its weight slabs and reuses the persisted
autotuner plan cache (``results/plans/``, auto-loaded at engine build),
so a replacement worker serves bit-identical logits to the one that died.

The worker exits on a closed pipe (supervisor death) — no orphan
processes hold the device.  On a TPU host the supervisor hands each worker
:func:`single_chip_env` in its spec; the worker applies it before its first
JAX call, so its runtime sees one chip of its own.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

__all__ = ["WorkerModel", "WorkerSpec", "single_chip_env", "worker_main"]


def single_chip_env(chip: int, port: int) -> Dict[str, str]:
    """Environment that confines one process's TPU runtime to ``chip`` of
    its host: a one-chip, one-process slice with its own coordination
    ``port``.  libtpu reads it when JAX brings its backend up, and a
    subset of the host's chips lets one process per chip load it."""
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


@dataclass(frozen=True)
class WorkerModel:
    """One model a worker serves: everything needed to rebuild its engine
    from scratch in a fresh process (spawn pickles this)."""
    name: str
    cfg: object                     # model config (frozen dataclass)
    scfg: object                    # CnnServeConfig
    seed: int = 0


@dataclass(frozen=True)
class WorkerSpec:
    """A worker's full build recipe — respawn == spawn(same spec)."""
    name: str
    models: Tuple[WorkerModel, ...]
    ckpt_dir: Optional[str] = None  # model params under <ckpt_dir>/<model>/
    warm: bool = True               # compile every bucket before 'ready'
    slot_budget: Optional[int] = None
    keep_checkpoints: int = 3
    env: Tuple[Tuple[str, str], ...] = ()   # set before JAX starts


@dataclass
class _WorkerState:
    registry: object
    params: dict                    # model -> params pytree
    restored: dict                  # model -> restored step (None = init)
    live: Dict[int, tuple] = field(default_factory=dict)  # uid -> (model, req)
    ckpt_step: int = 0
    oracles: dict = field(default_factory=dict)  # model -> (forward, params)


def _model_ckpt_dir(spec: WorkerSpec, model: str) -> Optional[str]:
    return os.path.join(spec.ckpt_dir, model) if spec.ckpt_dir else None


def _build(spec: WorkerSpec) -> _WorkerState:
    """Registry construction + crash-consistent param recovery + warmup."""
    import jax

    from ..checkpoint import checkpoint as ckpt
    from ..models import model_for
    from .cnn import ImageRequest
    from .registry import ModelRegistry

    reg = ModelRegistry(slot_budget=spec.slot_budget)
    params, restored = {}, {}
    for wm in spec.models:
        mod = model_for(wm.cfg)
        p = mod.init(jax.random.PRNGKey(wm.seed), wm.cfg)
        d = _model_ckpt_dir(spec, wm.name)
        step = ckpt.latest_intact_step(d) if d else None
        if step is not None:
            # restore into the init structure: the intact-step scan already
            # skipped any torn latest checkpoint
            p = ckpt.restore(d, {"step": 0, "params": p},
                             step=step)["params"]
        params[wm.name] = p
        restored[wm.name] = step
        eng = reg.register(wm.name, wm.cfg, wm.scfg, params=p, seed=wm.seed)
        if spec.warm:
            rng = np.random.default_rng(wm.seed)
            for b in eng.buckets:
                for _ in range(b):
                    eng.submit(ImageRequest(image=rng.standard_normal(
                        (wm.cfg.image_size, wm.cfg.image_size,
                         wm.cfg.in_channels)).astype(np.float32)))
                eng.run_until_done()
            eng.reset_metrics()
    return _WorkerState(registry=reg, params=params, restored=restored)


def _retire_batch(st: _WorkerState) -> list:
    """Drain every terminal request out of the live table."""
    out = []
    for uid in list(st.live):
        model, req = st.live[uid]
        if req.done:
            out.append({"uid": uid, "model": model, "status": "done",
                        "logits": np.asarray(req.logits),
                        "label": req.label,
                        "bucket": req.served_bucket,
                        "row": req.served_row,
                        "group": req.served_group,
                        "attempts": req.attempts})
        elif req.expired:
            out.append({"uid": uid, "model": model, "status": "expired",
                        "expire_reason": req.expire_reason,
                        "attempts": req.attempts})
        else:
            continue
        del st.live[uid]
    return out


def _reference(st: _WorkerState, spec: WorkerSpec, model: str, images):
    """Jitted direct forward of ``model`` on one padded batch — the
    failover parity oracle, run where a chip is.  Its params come from
    ``init(seed)``, not from what this worker serves, so a checkpoint
    restored with wrong values cannot agree with itself."""
    import jax

    from ..models import model_for

    if model not in st.oracles:
        wm = next(m for m in spec.models if m.name == model)
        mod = model_for(wm.cfg)
        st.oracles[model] = (
            jax.jit(lambda p, x: mod.apply(p, wm.cfg, x)),
            mod.init(jax.random.PRNGKey(wm.seed), wm.cfg))
    fn, params = st.oracles[model]
    return np.asarray(fn(params, images))


def _accounting(st: _WorkerState) -> dict:
    return {name: eng.accounting()
            for name, eng in st.registry.engines.items()}


def worker_main(conn, spec: WorkerSpec) -> None:
    """Child-process entry point (top-level so ``spawn`` can import it)."""
    # the chip assignment must be in place before JAX brings a backend up
    os.environ.update(dict(spec.env))
    from .cnn import ImageRequest
    from .compile_cache import enable_compile_cache

    enable_compile_cache()

    try:
        st = _build(spec)
    except BaseException as e:          # surface build failures to parent
        try:
            conn.send({"op": "ready", "ok": False, "worker": spec.name,
                       "error": f"{type(e).__name__}: {e}"})
        finally:
            conn.close()
        raise
    conn.send({"op": "ready", "ok": True, "worker": spec.name, "pid":
               os.getpid(), "models": [m.name for m in spec.models],
               "restored": st.restored})

    while True:
        try:
            msg = conn.recv()
        except (EOFError, OSError):     # supervisor died: don't linger
            return
        op = msg.get("op")
        reply = {"op": op, "seq": msg.get("seq"), "worker": spec.name}
        if op == "submit":
            req = ImageRequest(image=msg["image"], uid=msg["uid"],
                               deadline_ms=msg.get("deadline_ms"),
                               retries=msg.get("retries", 2))
            accepted = st.registry.submit(msg["model"], req)
            if accepted:
                st.live[req.uid] = (msg["model"], req)
            reply.update(accepted=accepted)
        elif op == "step":
            for _ in range(max(int(msg.get("n", 1)), 1)):
                st.registry.step()
            reply.update(drained=st.registry.idle)
        elif op == "retire_batch":
            reply.update(results=_retire_batch(st))
        elif op == "heartbeat":
            reply.update(alive=True, pid=os.getpid(),
                         inflight=len(st.live),
                         accounting=_accounting(st))
        elif op == "checkpoint":
            from ..checkpoint import checkpoint as ckpt
            st.ckpt_step += 1
            paths = {}
            for name, p in st.params.items():
                paths[name] = ckpt.save(
                    _model_ckpt_dir(spec, name),
                    {"step": st.ckpt_step, "params": p},
                    keep=spec.keep_checkpoints)
            reply.update(paths=paths, step=st.ckpt_step)
        elif op == "reference":
            reply.update(logits=_reference(st, spec, msg["model"],
                                           msg["images"]))
        elif op == "stall":
            time.sleep(msg.get("delay_ms", 0.0) / 1e3)
            reply.update(stalled_ms=msg.get("delay_ms", 0.0))
        elif op == "shutdown":
            reply.update(bye=True)
            try:
                conn.send(reply)
            finally:
                conn.close()
            return
        else:
            reply.update(error=f"unknown op {op!r}")
        try:
            conn.send(reply)
        except (BrokenPipeError, OSError):
            return
