"""Supervised multi-process serving: protocol, heartbeats, failover,
crash-consistent restart, and the fleet accounting invariant.

These tests spawn real worker processes (multiprocessing ``spawn``
context — each worker owns its own JAX runtime), so they are the slowest
in the suite; configs are shrunk (35px AlexNet, max_batch=2) to keep the
per-worker build short.  The invariant under test everywhere::

    submitted == completed + shed + expired          (fleet-wide, drained)

must hold across worker kills, stalls, and respawns — no request is ever
silently lost — and every failed-over request's served logits must
bit-match a jitted direct forward at the exact padded bucket shape it
was served in (crash-consistent restart: respawned workers rebuild
bit-identical engines from checkpoint + plan cache).
"""
import dataclasses
import os
import time

import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import (CnnServeConfig, FaultSpec, ImageRequest,
                           Supervisor, SupervisorConfig, WorkerModel)


@pytest.fixture(scope="module")
def small():
    cfg = dataclasses.replace(get_config("alexnet").reduced(),
                              image_size=35)
    scfg = CnnServeConfig(max_batch=2, staging_depth=2,
                          retry_backoff_ms=0.5)
    return cfg, scfg


def _images(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    ).astype(np.float32)


def _sup(cfg, scfg, **kw):
    sup_kw = {}
    for k in ("ckpt_dir", "chaos", "chaos_workers", "seed"):
        if k in kw:
            sup_kw[k] = kw.pop(k)
    cfg_kw = dict(n_workers=2, max_restarts=2, checkpoint_on_start=False,
                  heartbeat_timeout_ms=500.0)
    cfg_kw.update(kw)
    return Supervisor((WorkerModel("alexnet", cfg, scfg,
                                   seed=sup_kw.get("seed", 0)),),
                      SupervisorConfig(**cfg_kw), **sup_kw)


def _drain_ok(sup, n_submitted):
    acc = sup.run_until_done(max_steps=2000)
    assert acc["balanced"] and acc["in_flight"] == 0, acc
    assert acc["submitted"] == n_submitted
    assert acc["submitted"] == (acc["completed"] + acc["shed"]
                                + acc["expired"]), acc
    return acc


def _await_respawn(sup, name, timeout_s=300.0):
    """Pump until the respawned worker's ready handshake lands."""
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < timeout_s:
        sup.step()
        h = sup.workers[name]
        if h.alive:
            return h
        time.sleep(0.2)
    raise AssertionError(f"{name} never came back")


# ---------------------------------------------------------------------------
def test_protocol_roundtrip_heartbeat_and_bitmatch(small, tmp_path):
    """The pickle-over-pipe protocol end to end: submit/step/retire via
    the pump, heartbeat accounting snapshots, a checkpoint op that writes
    an intact (crc-verified) checkpoint — and every served logit
    bit-matching the direct forward at its padded bucket shape."""
    from repro import checkpoint as ckpt

    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=1, ckpt_dir=str(tmp_path / "ck"))
    with sup:
        imgs = _images(cfg, 5)
        reqs = [ImageRequest(image=im) for im in imgs]
        for r in reqs:
            assert sup.submit("alexnet", r)
        _drain_ok(sup, 5)
        assert all(r.done for r in reqs)
        # worker-side accounting arrives via heartbeat — which runs at the
        # top of a pump, so the snapshot trails the work by one step
        sup.step()
        wacc = sup.workers["w0"].last_accounting
        assert wacc["alexnet"]["completed"] == 5
        # served logits bit-match the padded-shape oracle, cross-process
        par = sup.verify_bit_parity(uids=[r.uid for r in reqs])
        assert par["checked"] == 5 and par["mismatched"] == 0, par
        # provenance was stamped by the engine and survived the pipe
        assert all(r.served_bucket in (1, 2) for r in reqs)
        assert all(r.uid in r.served_group for r in reqs)
        # checkpoint RPC writes a crc-intact checkpoint
        rep = sup.checkpoint()
        d = os.path.join(str(tmp_path / "ck"), "alexnet")
        step = rep["step"]
        ok, problems = ckpt.verify_step(d, step)
        assert ok, problems
        assert ckpt.latest_intact_step(d) == step


def test_stall_trips_heartbeat_but_worker_survives(small):
    """worker.stall chaos: the worker sleeps through a heartbeat deadline
    — the health ladder records the miss, but below the quarantine
    threshold the worker recovers (stale replies dropped by seq) and
    nothing is killed or lost."""
    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=2,
               heartbeat_timeout_ms=150.0, miss_threshold=6,
               chaos={"worker.stall": FaultSpec(at=(1,), delay_ms=350.0,
                                                limit=1)},
               chaos_workers=("w0",))
    with sup:
        imgs = _images(cfg, 8)
        reqs = [ImageRequest(image=im) for im in imgs]
        # two waves: the stall fires at pump opportunity 1, so wave two
        # must still be in flight when it lands
        for r in reqs[:4]:
            sup.submit("alexnet", r)
        sup.step()                          # opportunity 0: no stall
        for r in reqs[4:]:
            sup.submit("alexnet", r)
        acc = _drain_ok(sup, 8)
        assert acc["completed"] == 8
        h = sup.workers["w0"]
        assert h.injector.summary()["worker.stall"]["fired"] == 1
        assert h.monitor.failures_total >= 1      # the miss was recorded
        assert not h.deaths                       # ...but no kill
        assert h.restarts == 0


def test_mid_flight_kill_fails_over_zero_lost_bit_identical(small):
    """SIGKILL a worker with queued + in-flight requests: survivors pick
    the orphans up at their remaining deadline, the fleet invariant holds,
    and every failed-over logit bit-matches the padded-shape oracle."""
    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=2)
    with sup:
        imgs = _images(cfg, 10)
        reqs = [ImageRequest(image=im, deadline_ms=60_000.0)
                for im in imgs]
        for r in reqs:
            sup.submit("alexnet", r)
        assert len(sup.workers["w0"].inflight) > 0
        sup.kill_worker("w0", "test-kill")
        acc = _drain_ok(sup, 10)
        assert acc["completed"] == 10 and acc["failed_over"] > 0
        par = sup.verify_bit_parity()
        assert par["checked"] == sup.failed_over
        assert par["mismatched"] == 0, par
        kinds = [e["event"] for e in sup.events]
        assert "death" in kinds
        assert sup.workers["w0"].restarts == 1    # respawn in flight/ready


def test_crash_consistent_restart_restores_intact_checkpoint(small,
                                                             tmp_path):
    """Kill a worker whose model has checkpoints on disk, with the
    *latest* checkpoint torn: the respawn must fall back to the previous
    intact step (crc manifest scan), rebuild, and serve bit-identically."""
    cfg, scfg = small
    ckpt_dir = str(tmp_path / "ck")
    sup = _sup(cfg, scfg, n_workers=2, ckpt_dir=ckpt_dir,
               checkpoint_on_start=True)
    with sup:
        sup.checkpoint()                  # step 2 (start() wrote step 1)
        d = os.path.join(ckpt_dir, "alexnet")
        # tear the newest checkpoint, as a crash mid-write would
        leaves = [f for f in os.listdir(os.path.join(d, "step_0000000002"))
                  if f.endswith(".npy")]
        os.remove(os.path.join(d, "step_0000000002", leaves[0]))

        imgs = _images(cfg, 4)
        reqs = [ImageRequest(image=im, deadline_ms=120_000.0)
                for im in imgs]
        for r in reqs:
            sup.submit("alexnet", r)
        sup.kill_worker("w0", "test-kill")
        _drain_ok(sup, 4)
        h = _await_respawn(sup, "w0")
        # the respawn skipped the torn step 2 (the integrity warning fires
        # in the child process) and restored intact step 1
        assert h.restored == {"alexnet": 1}, h.restored
        # and serves bit-identically: route fresh traffic through w0 only
        sup.workers["w1"].alive = False   # force routing to the respawn
        more = [ImageRequest(image=im) for im in _images(cfg, 3, seed=9)]
        for r in more:
            assert sup.submit("alexnet", r)
        sup.workers["w1"].alive = True
        acc = sup.run_until_done(max_steps=2000)
        assert acc["balanced"] and all(r.done for r in more)
        par = sup.verify_bit_parity(uids=[r.uid for r in more])
        assert par["checked"] == 3 and par["mismatched"] == 0, par


def test_parity_oracle_catches_a_wrong_restored_leaf(small, tmp_path):
    """The oracle's params come from init(seed), not from the worker's
    restored checkpoint: a checkpoint that is intact on disk but holds a
    wrong leaf value is served, and every served logit then mismatches."""
    import jax

    from repro import checkpoint as ckpt
    from repro.models import model_for

    cfg, scfg = small
    p = model_for(cfg).init(jax.random.PRNGKey(0), cfg)
    p["fc8"]["b"] = p["fc8"]["b"] + 1.0             # the logits' bias
    ckpt_dir = str(tmp_path / "ck")
    ckpt.save(os.path.join(ckpt_dir, "alexnet"), {"step": 1, "params": p})
    sup = _sup(cfg, scfg, n_workers=1, ckpt_dir=ckpt_dir)
    with sup:
        assert sup.workers["w0"].restored == {"alexnet": 1}
        reqs = [ImageRequest(image=im) for im in _images(cfg, 3)]
        for r in reqs:
            assert sup.submit("alexnet", r)
        _drain_ok(sup, 3)
        par = sup.verify_bit_parity(uids=[r.uid for r in reqs])
    assert par["checked"] == 3 and par["mismatched"] == 3, par


def test_accounting_invariant_under_mixed_process_chaos(small):
    """Property: the fleet invariant holds across a mixed seeded chaos
    schedule (crashes + stalls) over traffic spanning every bucket
    padding, with deadlines tight enough that some requests expire."""
    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=2, seed=3,
               heartbeat_timeout_ms=200.0,
               chaos={"worker.crash": FaultSpec(at=(3,), limit=1),
                      "worker.stall": FaultSpec(rate=0.15, delay_ms=250.0,
                                                limit=2)},
               chaos_workers=("w0", "w1"))
    with sup:
        rng = np.random.default_rng(3)
        submitted = 0
        # group sizes 1..max_batch exercise every bucket padding; a mix
        # of no-deadline and tight-deadline requests exercises expiry
        for burst in (1, 2, 1, 2, 2, 1, 2, 2):
            for _ in range(burst):
                dl = 25.0 if rng.uniform() < 0.3 else 60_000.0
                sup.submit("alexnet", ImageRequest(
                    image=rng.standard_normal(
                        (cfg.image_size, cfg.image_size,
                         cfg.in_channels)).astype(np.float32),
                    deadline_ms=dl, retries=2))
                submitted += 1
            sup.step()
        acc = _drain_ok(sup, submitted)
        assert acc["completed"] > 0
        # the seeded crash fired (or the worker died trying)
        fired = sum((h.injector.summary().get("worker.crash", {})
                     .get("fired", 0)) for h in sup.workers.values()
                    if h.injector)
        assert fired >= 1
        # every completed request bit-matches its padded-shape oracle
        done = [u for u, (m, r) in sup.requests.items() if r.done]
        par = sup.verify_bit_parity(uids=done)
        assert par["mismatched"] == 0, par


def test_tpu_host_gives_each_worker_its_own_chip(small, monkeypatch):
    """On a TPU host worker k is confined to chip k before its JAX runtime
    starts, and the supervisor refuses more workers than chips."""
    from repro.serving import supervisor as sv
    monkeypatch.setattr(sv, "tpu_chip_count", lambda: 4)
    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=4)          # built, never started
    envs = [dict(h.spec.env) for h in sup.workers.values()]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
               and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    with pytest.raises(ValueError, match="only 4 TPU chips"):
        _sup(cfg, scfg, n_workers=5)


def test_cpu_workers_start_without_chip_env(small, monkeypatch):
    from repro.serving import supervisor as sv
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert sv.tpu_chip_count() is None
    cfg, scfg = small
    sup = _sup(cfg, scfg, n_workers=2)
    assert all(h.spec.env == () for h in sup.workers.values())


def test_parent_never_initialises_a_backend():
    """The supervisor process serves, checks failover parity (recomputed
    by a worker) and shuts down without ever bringing up a JAX backend —
    on a TPU host that backend would take a chip from the workers."""
    import subprocess
    import sys
    import textwrap

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = textwrap.dedent("""
        import dataclasses
        import numpy as np
        from jax._src import xla_bridge
        from repro.configs import get_config
        from repro.serving import (CnnServeConfig, ImageRequest, Supervisor,
                                   SupervisorConfig, WorkerModel)
        cfg = dataclasses.replace(get_config("alexnet").reduced(),
                                  image_size=35)
        sup = Supervisor(
            (WorkerModel("alexnet", cfg, CnnServeConfig(max_batch=2)),),
            SupervisorConfig(n_workers=1, checkpoint_on_start=False))
        rng = np.random.default_rng(0)
        reqs = [ImageRequest(image=rng.standard_normal(
            (35, 35, 3)).astype(np.float32)) for _ in range(3)]
        with sup:
            for r in reqs:
                sup.submit("alexnet", r)
            sup.run_until_done(max_steps=2000)
            par = sup.verify_bit_parity(uids=[r.uid for r in reqs])
        assert par["checked"] == 3 and par["mismatched"] == 0, par
        assert not xla_bridge.backends_are_initialized()
        print("OK")
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "OK" in r.stdout
