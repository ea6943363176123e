#!/usr/bin/env python3
"""Smoke check on the chip: AlexNet at its published width, served on TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # the paths that span the host's chips

One chip: ``get_config("alexnet")`` at full width (227 px; conv channels
96-256-384-384-256; FC 4096-4096-1000) with random weights from a seed, on
the Pallas route, served through ``CnnEngine`` with buckets 1, 2, 4 and 8 —
the path ``python -m repro.launch.serve --arch alexnet --full --route
pallas`` takes.  It fails unless every conv layer resolves to a Pallas
kernel, every bucket's compiled forward holds at least five Pallas kernels
(``tpu_custom_call``), every request completes with no failed batch, no
degradation and a healthy engine, the conv stack's features (conv1-conv5,
before any FC layer) match the lax conv route at the highest matmul
precision within ``FEATURE_TOL`` while a bfloat16 control does not, and the
served logits match a reference forward — the same model on the lax conv /
plain FC route at the highest matmul precision — within ``LOGIT_TOL``, with
top-1 labels agreeing on at least ``TOP1_MIN`` of the images.

``--chips 4`` runs only what spans chips, each part in a child process of
its own, one after another, while this process stays off JAX: the
one-chip engine, the data-parallel engine (``shard_map`` over all chips,
with no all-gather of the image batch), and a supervised fleet of four
one-chip workers; both must match the one-chip engine's logits.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Where JAX finds no TPU, or any check fails, the script exits non-zero
without printing it.  Timings printed here are a smoke check, not a
benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
MAX_BATCH = 8
# request waves, each served to completion before the next: full buckets
# of 8, partial ones (5 -> 8, 3 -> 4) and the small buckets 1 and 2
WAVES = (8, 8, 5, 3, 1, 2, 8, 1)
# max |features - reference| over max |reference| for the Pallas conv
# stack alone, against the lax conv route at the highest precision.  Its
# float32 GEMMs round each product at 2^-24 and the Winograd transforms
# add a few ulps more (1.1e-6 measured on a v5e); bfloat16 operands, each
# rounded at 2^-8, exceed it (the bfloat16 control measured 5.8e-3, and
# the kernels at Mosaic's default precision 1.8e-2).  The control must
# exceed it, or the bound proves nothing
FEATURE_TOL = 1e-3
# max |served - reference| over max |reference|.  The reference runs at
# the highest matmul precision; XLA's default TPU precision for float32
# matmuls (the served FC layers) rounds each operand to bfloat16, a
# relative error of up to 2^-8 per operand over three layers: 4.1e-3
# measured on a v5e.  Conv kernels with bfloat16 operands measured 1.04e-2
LOGIT_TOL = 1e-2
# least share of images whose served top-1 label is the reference's: a
# label may flip only where two logits lie within the logit error
TOP1_MIN = 0.9
# the data-parallel engine and the fleet against the one-chip engine: the
# same bound.  XLA lowers a one-row float32 dot (an FC layer on a batch of
# one) to a full-precision VPU reduction and larger ones to MXU passes on
# bfloat16-rounded operands, so an image served in another bucket shape --
# by a fleet worker, or in one chip's slice of a data-parallel bucket --
# rounds differently
PARITY_TOL = LOGIT_TOL


class SmokeFailure(RuntimeError):
    """A check failed; the message says which."""


# ---------------------------------------------------------------------------
# phases, each a function of the model config
# ---------------------------------------------------------------------------
def device_report() -> dict:
    """The default device as JAX reports it."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def pallas_config(cfg):
    """``cfg`` on the Pallas route, as ``launch/serve.py --route pallas``
    sets it; fails unless every conv layer resolves to a Pallas kernel."""
    from repro.launch.serve import apply_cnn_route
    from repro.models.alexnet import layer_routes

    cfg = apply_cnn_route(cfg, "pallas")
    routes = layer_routes(cfg)
    print("conv routes: " + " ".join(f"{n}={r}" for n, r in routes))
    off = [n for n, r in routes if not r.startswith("pallas-")]
    if off:
        raise SmokeFailure(f"layers off the Pallas route: {off}")
    return cfg


def build_engine(cfg, *, data_parallel: bool = False):
    """``CnnEngine`` with buckets up to ``MAX_BATCH`` and every bucket
    compiled ahead of traffic; prints the compile seconds per bucket."""
    from repro.serving import CnnEngine, CnnServeConfig

    eng = CnnEngine(cfg, CnnServeConfig(max_batch=MAX_BATCH,
                                        data_parallel=data_parallel),
                    seed=SEED)
    secs = eng.precompile()
    print("compile seconds per bucket: " + " ".join(
        f"{b}={s:.2f}" for b, s in secs.items()))
    return eng


def kernel_counts(eng) -> dict:
    """Pallas kernels (``tpu_custom_call``) in each bucket's compiled
    forward."""
    return {b: exe.as_text().count('custom_call_target="tpu_custom_call"')
            for b, exe in sorted(eng.executables.items())}


def check_kernels(counts: dict, layers: int):
    print("tpu_custom_call per bucket: " + " ".join(
        f"{b}={n}" for b, n in counts.items()))
    short = {b: n for b, n in counts.items() if n < layers}
    if short:
        raise SmokeFailure(f"buckets with fewer than {layers} Pallas "
                           f"kernels: {short}")


def seeded_images(cfg, n: int, seed: int = SEED):
    import numpy as np

    rng = np.random.default_rng(seed)
    return rng.standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.in_channels)
    ).astype(np.float32)


def serve(eng, images, waves=WAVES):
    """Submit ``images`` in ``waves``, serving each to completion; returns
    the logits in submission order and the seconds spent serving."""
    import numpy as np

    from repro.serving import ImageRequest

    assert sum(waves) == len(images), (waves, len(images))
    reqs, t0, i = [], time.perf_counter(), 0
    for n in waves:
        wave = [ImageRequest(image=im) for im in images[i:i + n]]
        for r in wave:
            eng.submit(r)
        eng.run_until_done()
        reqs += wave
        i += n
    seconds = time.perf_counter() - t0
    if not all(r.done for r in reqs):
        raise SmokeFailure(f"{sum(not r.done for r in reqs)} requests "
                           f"not served")
    return np.stack([np.asarray(r.logits) for r in reqs]), seconds


def check_serving(eng):
    """Every request completed, nothing failed or degraded, the engine is
    healthy and its accounting balances."""
    s = eng.stats()
    acc = s["accounting"]
    print(f"served {acc['completed']}/{acc['submitted']} in "
          f"{s['batches_run']} batches {s['bucket_counts']}; "
          f"batches_failed={s['batches_failed']} "
          f"degradations={s['degradations']} "
          f"health={s['health']['state']} balanced={acc['balanced']}")
    bad = []
    if acc["completed"] != acc["submitted"]:
        bad.append("completed != submitted")
    if s["batches_failed"]:
        bad.append(f"{s['batches_failed']} failed batches")
    if s["degradations"]:
        bad.append(f"degradations {s['degradations']}")
    if s["health"]["state"] != "healthy":
        bad.append(f"health {s['health']['state']}")
    if not acc["balanced"]:
        bad.append(f"accounting {acc}")
    if bad:
        raise SmokeFailure("; ".join(bad))


def reference_logits(cfg, params, images, batch: int = MAX_BATCH):
    """The same model on the lax conv / plain FC route at the highest
    matmul precision, on the default device."""
    import dataclasses

    import jax
    import numpy as np

    from repro.models import alexnet

    ref_cfg = dataclasses.replace(cfg, use_winograd=False, use_pallas=False)
    fwd = jax.jit(lambda p, x: alexnet.apply(p, ref_cfg, x))
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(images), batch):
            out.append(np.asarray(fwd(params, images[i:i + batch])))
    return np.concatenate(out)


def feature_errors(cfg, params, images, batch: int = 4):
    """Relative errors of two conv stacks' features (conv1-conv5 with
    their LRN/pool epilogues, flattened) against the lax conv route at the
    highest matmul precision: the Pallas datapath as served (no precision
    context, as the engine runs it), and a bfloat16 control (the lax route
    at ``bfloat16`` precision).  Returns ``(pallas, control)``."""
    import contextlib
    import dataclasses

    import jax
    import numpy as np

    from repro.models import alexnet

    lax_cfg = dataclasses.replace(cfg, use_winograd=False, use_pallas=False)

    def run(c, precision):
        fwd = jax.jit(lambda p, x: alexnet.features(p, c, x))
        ctx = (jax.default_matmul_precision(precision) if precision
               else contextlib.nullcontext())
        with ctx:
            return np.concatenate([np.asarray(fwd(params, images[i:i + batch]))
                                   for i in range(0, len(images), batch)])

    ref = run(lax_cfg, "highest")
    scale = float(np.abs(ref).max())
    return tuple(float(np.abs(run(c, p) - ref).max()) / scale
                 for c, p in ((cfg, None), (lax_cfg, "bfloat16")))


def check_features(err: float, control: float, tol: float = FEATURE_TOL):
    """The Pallas features within ``tol`` of the reference, and the
    bfloat16 control outside it."""
    print(f"conv features vs reference: relative error {err:.3e} (limit "
          f"{tol:.0e}); bfloat16 control {control:.3e} (must exceed it)")
    if not err <= tol:
        raise SmokeFailure(f"conv features: relative error {err:.3e} > "
                           f"{tol}")
    if not control > tol:
        raise SmokeFailure(f"bfloat16 control {control:.3e} within {tol}: "
                           f"the bound does not tell float32 from bfloat16")


def compare(got, ref, tol: float, what: str) -> float:
    """Max logit error relative to the reference's largest logit, checked
    against ``tol``, and the share of top-1 labels that agree, checked
    against ``TOP1_MIN``.  Returns the relative error."""
    import numpy as np

    if not np.isfinite(got).all():
        raise SmokeFailure(f"{what}: non-finite logits")
    err = float(np.abs(got - ref).max())
    rel = err / float(np.abs(ref).max())
    agree = float((got.argmax(1) == ref.argmax(1)).mean())
    print(f"{what}: max logit error {err:.3e} (relative {rel:.3e}, "
          f"limit {tol:.0e}); top-1 agreement {agree:.3f} "
          f"(limit {TOP1_MIN})")
    if rel > tol:
        raise SmokeFailure(f"{what}: relative logit error {rel:.3e} > {tol}")
    if agree < TOP1_MIN:
        raise SmokeFailure(f"{what}: top-1 agreement {agree:.3f} < "
                           f"{TOP1_MIN}")
    return rel


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------
def require_tpu() -> dict:
    dev = device_report()
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    if dev["platform"] != "tpu":
        raise SmokeFailure(f"JAX found no TPU (default platform "
                           f"{dev['platform']!r})")
    return dev


def one_chip() -> dict:
    from repro.configs import get_config
    from repro.serving.compile_cache import enable_compile_cache

    enable_compile_cache()
    dev = require_tpu()
    cfg = pallas_config(get_config("alexnet"))
    eng = build_engine(cfg)
    check_kernels(kernel_counts(eng), len(cfg.conv_channels))
    images = seeded_images(cfg, sum(WAVES))
    logits, seconds = serve(eng, images)
    check_serving(eng)
    print(f"smoke timing (not a benchmark): {len(images)} images in "
          f"{seconds:.3f}s = {len(images) / seconds:.1f} img/s")
    check_features(*feature_errors(cfg, eng.params, images))
    compare(logits, reference_logits(cfg, eng.params, images), LOGIT_TOL,
            "served vs reference")
    return dev


# ---------------------------------------------------------------------------
# four chips: each part in a child process of its own
# ---------------------------------------------------------------------------
def _part_one_chip(out: str):
    import numpy as np

    from repro.configs import get_config

    dev = require_tpu()
    cfg = pallas_config(get_config("alexnet"))
    eng = build_engine(cfg)
    logits, _ = serve(eng, seeded_images(cfg, sum(WAVES)))
    check_serving(eng)
    np.save(out, logits)
    return dev


def _part_data_parallel(out: str):
    import numpy as np

    from repro.configs import get_config

    dev = require_tpu()
    cfg = pallas_config(get_config("alexnet"))
    eng = build_engine(cfg, data_parallel=True)
    print(f"data-parallel mesh: {eng.mesh.devices.size} devices")
    check_kernels(kernel_counts(eng), len(cfg.conv_channels))
    gathered = [b for b, exe in eng.executables.items()
                if "all-gather" in exe.as_text()]
    if gathered:
        raise SmokeFailure(f"buckets gathering across chips: {gathered}")
    logits, _ = serve(eng, seeded_images(cfg, sum(WAVES)))
    check_serving(eng)
    np.save(out, logits)
    return dev


def _part_fleet(out: str):
    import numpy as np

    from repro.configs import get_config
    from repro.serving import (CnnServeConfig, ImageRequest, Supervisor,
                               SupervisorConfig, WorkerModel)
    from repro.serving.supervisor import tpu_chip_count

    chips = tpu_chip_count()
    if not chips:
        raise SmokeFailure("no TPU chips on this host")
    cfg = pallas_config(get_config("alexnet"))
    scfg = CnnServeConfig(max_batch=MAX_BATCH)
    sup = Supervisor((WorkerModel(cfg.name, cfg, scfg, seed=SEED),),
                     SupervisorConfig(n_workers=chips,
                                      checkpoint_on_start=False))
    images = seeded_images(cfg, sum(WAVES))
    reqs = [ImageRequest(image=im) for im in images]
    with sup:
        for r in reqs:
            sup.submit(cfg.name, r)
        acc = sup.run_until_done()
        served = sorted({e["worker"] for e in sup.events
                         if e["event"] == "spawn"})
        lost = [e for e in sup.events if e["event"] != "spawn"]
    print(f"fleet: {chips} one-chip workers {served}; completed "
          f"{acc['completed']}/{acc['submitted']} balanced={acc['balanced']}")
    if acc["completed"] != len(reqs) or not acc["balanced"]:
        raise SmokeFailure(f"fleet accounting {acc}")
    if len(served) != chips or lost:
        raise SmokeFailure(f"fleet workers {served} of {chips}; {lost}")
    np.save(out, np.stack([np.asarray(r.logits) for r in reqs]))
    return {"platform": "tpu", "count": chips}


_PARTS = {"one_chip": _part_one_chip, "data_parallel": _part_data_parallel,
          "fleet": _part_fleet}


def _child(part: str, out: str):
    """Entry point of one ``--chips 4`` part, in its own process."""
    from repro.serving.compile_cache import enable_compile_cache

    enable_compile_cache()
    try:
        dev = _PARTS[part](out)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
    print(json.dumps(dev))


def four_chips() -> dict:
    """Run each part in a child process, one after the other, and compare
    logits; this process never imports JAX."""
    import numpy as np

    logits, reports = {}, {}
    with tempfile.TemporaryDirectory() as tmp:
        for part in _PARTS:
            out = os.path.join(tmp, f"{part}.npy")
            print(f"--- {part}", flush=True)
            r = subprocess.run(
                [sys.executable, "-c",
                 f"import chip_smoke; chip_smoke._child({part!r}, {out!r})"],
                cwd=ROOT, stdout=subprocess.PIPE, text=True, timeout=1500)
            lines = r.stdout.strip().splitlines()
            print("\n".join(lines[:-1]), flush=True)
            if r.returncode != 0:
                raise SmokeFailure(f"{part} exited {r.returncode}")
            reports[part] = json.loads(lines[-1])
            logits[part] = np.load(out)
    for part in ("data_parallel", "fleet"):
        compare(logits[part], logits["one_chip"], PARITY_TOL,
                f"{part} vs one-chip engine")
    return reports["data_parallel"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the paths that span chips (data-parallel "
                         "engine, four one-chip workers) against the "
                         "one-chip engine")
    args = ap.parse_args(argv)
    try:
        dev = four_chips() if args.chips == 4 else one_chip()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
