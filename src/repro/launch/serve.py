"""Serving launcher: continuous-batching engine over synthetic requests.

``python -m repro.launch.serve --arch llama3.2-3b --requests 16``   (decode)
``python -m repro.launch.serve --arch alexnet --requests 32``       (images)

LM archs go through the token-decode :class:`Engine`; ``alexnet`` (the
paper's own workload) goes through the bucketed, double-buffered
:class:`CnnEngine` and reports img/s + latency percentiles (Tables 5-6).
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from ..configs import ASSIGNED, CNN_ARCHS, get_config
from ..models import model_for
from ..serving.compile_cache import enable_compile_cache
from ..serving import (CnnEngine, CnnServeConfig, Engine, FaultInjector,
                       FaultSpec, ImageRequest, Request, ServeConfig,
                       Supervisor, SupervisorConfig, WorkerModel,
                       derive_seed)

CNN_ROUTES = ("auto", "direct", "winograd", "pallas")


def apply_cnn_route(cfg, route: str):
    """Map a conv route name onto the CNN model config's route knobs.

    ``auto`` keeps the config's own preference; the explicit routes force
    every eligible conv through direct / pure-jnp Winograd / the Pallas
    kernel (interpret mode off-TPU), so the serving path can exercise the
    stream-buffered kernel end-to-end through :class:`CnnEngine`.
    """
    assert route in CNN_ROUTES, route
    if route == "auto" or getattr(cfg, "family", None) != "cnn":
        return cfg
    return dataclasses.replace(cfg, use_winograd=route != "direct",
                               use_pallas=route == "pallas")


def serve_supervised(cfg, args) -> int:
    """Supervised multi-process path: N worker processes behind one
    :class:`Supervisor` (heartbeats, failover re-dispatch, crash-consistent
    restart).  ``--kill-worker`` SIGKILLs worker w0 mid-run to demonstrate
    zero-loss failover; ``--chaos`` arms seeded per-worker process chaos."""
    cfg = apply_cnn_route(cfg, getattr(args, "route", "auto"))
    scfg = CnnServeConfig(max_batch=args.max_batch,
                          slo_ms=getattr(args, "slo_ms", None))
    chaos = None
    if getattr(args, "chaos", False):
        chaos = {"worker.crash": FaultSpec(rate=0.02, limit=1),
                 "worker.stall": FaultSpec(rate=0.05, delay_ms=50.0,
                                           limit=3)}
    sup = Supervisor((WorkerModel(cfg.name, cfg, scfg, seed=args.seed),),
                     SupervisorConfig(n_workers=args.workers,
                                      checkpoint_on_start=False),
                     seed=args.seed, chaos=chaos)
    rng = np.random.default_rng(args.seed)
    deadline_ms = getattr(args, "deadline_ms", None)
    reqs = [ImageRequest(image=rng.standard_normal(
                (cfg.image_size, cfg.image_size, cfg.in_channels))
                .astype(np.float32),
                deadline_ms=deadline_ms,
                retries=getattr(args, "retries", 2))
            for _ in range(args.requests)]
    # kill right after an even-indexed submit: round-robin puts those on
    # w0, so the SIGKILL demonstrably orphans an in-flight request
    kill_at = ((len(reqs) // 2) & ~1 if getattr(args, "kill_worker", False)
               else None)
    with sup:
        for i, r in enumerate(reqs):
            sup.submit(cfg.name, r)
            if kill_at is not None and i == kill_at:
                sup.kill_worker("w0", "operator:--kill-worker")
                kill_at = None
            sup.step()
        sup.run_until_done()
        acc = sup.accounting()
        lat = sup.latency.percentiles_ms()
        print(f"supervised fleet: {args.workers} workers, "
              f"completed {acc['completed']}/{acc['submitted']} "
              f"(shed={acc['shed']} expired={acc['expired']} "
              f"failed_over={acc['failed_over']}) "
              f"balanced={'yes' if acc['balanced'] else 'NO'}")
        print(f"latency p50={lat['p50']:.1f}ms p90={lat['p90']:.1f}ms "
              f"p99={lat['p99']:.1f}ms")
        if sup.failover_uids:
            par = sup.verify_bit_parity()
            print(f"failover bit-parity: {par['checked']} checked, "
                  f"{par['mismatched']} mismatched")
        deaths = [e for e in sup.events if e["event"] == "death"]
        if deaths:
            print("worker deaths: " + "; ".join(
                f"{e['worker']}({e['reason']})" for e in deaths))
    return acc["completed"]


def serve_images(cfg, args) -> int:
    """Image-classification serving path (paper §3.5/§3.7 regime)."""
    cfg = apply_cnn_route(cfg, getattr(args, "route", "auto"))
    if hasattr(cfg, "weight_prefetch"):
        prefetch = getattr(args, "prefetch", "on") == "on"
        cfg = dataclasses.replace(cfg, weight_prefetch=prefetch)
    sdc = getattr(args, "sdc", False) and hasattr(cfg, "sdc_abft")
    if sdc:
        # full SDC defense: ABFT checksums through the conv datapath,
        # pre-dispatch slab fingerprints, magnitude-bounded screen
        cfg = dataclasses.replace(cfg, sdc_abft=True)
    if hasattr(model_for(cfg), "layer_routes"):
        # per-layer resolved datapaths — `--route pallas` must show every
        # layer on a Pallas kernel, not a silent lax fallback — plus the
        # resolved §3.5 weight-stream mode (double-buffered DMA vs
        # synchronous fetches; lax/jnp routes have no in-kernel stream)
        routes = model_for(cfg).layer_routes(cfg)
        pallas_any = any(r.startswith("pallas") for _, r in routes)
        mode = (("on(dma-double-buffer)" if cfg.weight_prefetch
                 else "off(dma-sync)") if pallas_any else "n/a(no-dma-route)")
        print("conv routes: " + " ".join(f"{n}={r}" for n, r in routes)
              + f" | weight_prefetch={mode}")
    slo_ms = getattr(args, "slo_ms", None)
    scfg = CnnServeConfig(max_batch=args.max_batch,
                          data_parallel=args.data_parallel,
                          slo_ms=slo_ms,
                          dynamic_buckets=bool(
                              slo_ms and getattr(args, "dynamic_buckets",
                                                 False)),
                          admission=bool(slo_ms and getattr(args, "admission",
                                                            False)),
                          verify_slabs=sdc,
                          screen_abs_max=1e6 if sdc else None)
    faults = None
    if getattr(args, "chaos", False):
        # light seeded schedule: transient launches + non-finite logits,
        # enough to exercise retry/screen/health without stalling the run
        specs = {"launch.transient": FaultSpec(rate=0.1),
                 "retire.nonfinite": FaultSpec(rate=0.05)}
        if sdc:
            # SDC chaos: slab bit flips + plausible (finite) logit
            # corruption, exercised against the armed defense
            specs["slab.bitflip"] = FaultSpec(rate=0.1)
            specs["retire.plausible"] = FaultSpec(rate=0.05,
                                                  magnitude=1e8)
        faults = FaultInjector(
            seed=derive_seed(args.seed, cfg.name), specs=specs)
    eng = CnnEngine(cfg, scfg, seed=args.seed, faults=faults)
    rng = np.random.default_rng(args.seed)
    deadline_ms = getattr(args, "deadline_ms", None)
    retries = getattr(args, "retries", 2)
    reqs = [ImageRequest(image=rng.standard_normal(
                (cfg.image_size, cfg.image_size, cfg.in_channels))
                .astype(np.float32),
                deadline_ms=deadline_ms, retries=retries)
            for _ in range(args.requests)]
    for r in reqs:
        if scfg.admission:
            eng.try_submit(r)
        else:
            eng.submit(r)
    eng.run_until_done()
    s = eng.stats()
    done = sum(r.done for r in reqs)
    lat = s["latency_ms"]
    print(f"completed {done}/{len(reqs)} requests; "
          f"{s['imgs_per_s']:.1f} img/s over {s['batches_run']} batches "
          f"(avg occupancy {s['avg_occupancy']:.2f}, "
          f"buckets {s['bucket_counts']})")
    print(f"latency p50={lat['p50']:.1f}ms p90={lat['p90']:.1f}ms "
          f"p99={lat['p99']:.1f}ms")
    if slo_ms:
        print(f"slo={slo_ms:.1f}ms goodput={s['goodput_imgs_per_s']:.1f} "
              f"img/s shed={s['images_shed']} ladder={s['buckets']}")
    acc = s["accounting"]
    print(f"accounting submitted={acc['submitted']} "
          f"completed={acc['completed']} shed={acc['shed']} "
          f"expired={acc['expired']} "
          f"balanced={'yes' if acc['balanced'] else 'NO'} | "
          f"health={s['health']['state']} retried={s['images_retried']}"
          + (f" faults_fired={faults.total_fired}" if faults else ""))
    if sdc:
        d = s["sdc"]
        print(f"sdc abft=on verify_slabs=on detections={d['detections']} "
              f"slab_integrity_failures={d['slab_integrity_failures']} "
              f"screen_nonfinite={d['screen_nonfinite']} "
              f"screen_magnitude={d['screen_magnitude']}")
    if faults is not None:
        # per-point opportunity/fire audit — replays can be checked
        # against this line without parsing the full stats dump
        audit = " ".join(
            f"{p}={c['fired']}/{c['opportunities']}"
            for p, c in sorted(faults.summary().items()))
        print(f"fault audit (fired/opportunities): {audit}")
    return done


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    choices=ASSIGNED + CNN_ARCHS)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--data-parallel", action="store_true",
                    help="CNN path: shard buckets over all JAX devices")
    ap.add_argument("--route", default="auto", choices=CNN_ROUTES,
                    help="CNN path: conv route (pallas = stream-buffered "
                         "kernel, interpret mode off-TPU)")
    ap.add_argument("--prefetch", default="on", choices=("on", "off"),
                    help="CNN path: Pallas weight stream — double-buffered "
                         "manual-DMA filter prefetch (on) vs the same "
                         "copies run synchronously (off; bit-equal)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="CNN path: p99 latency SLO enabling the serving "
                         "policy layer (goodput accounting; see also "
                         "--dynamic-buckets / --admission)")
    ap.add_argument("--dynamic-buckets", action="store_true",
                    help="CNN path: SLO-driven bucket-ladder resizing")
    ap.add_argument("--admission", action="store_true",
                    help="CNN path: SLO-driven load shedding at submit")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="CNN path: per-request deadline; overdue requests "
                         "retire as expired (reported, never dropped)")
    ap.add_argument("--retries", type=int, default=2,
                    help="CNN path: per-request transient-failure retry "
                         "budget (exponential backoff)")
    ap.add_argument("--chaos", action="store_true",
                    help="CNN path: arm a seeded FaultInjector (transient "
                         "launch failures + non-finite logits) to exercise "
                         "the retry/screen/health machinery")
    ap.add_argument("--sdc", action="store_true",
                    help="CNN path: arm the silent-data-corruption defense "
                         "(ABFT checksums on the conv weight stream, "
                         "pre-dispatch slab fingerprints, magnitude-bounded "
                         "logit screen); with --chaos also injects slab bit "
                         "flips and plausible logit corruption")
    ap.add_argument("--workers", type=int, default=0,
                    help="CNN path: >0 serves through a Supervisor owning "
                         "this many worker processes (heartbeats, failover "
                         "re-dispatch, crash-consistent restart)")
    ap.add_argument("--kill-worker", action="store_true",
                    help="CNN path (--workers): SIGKILL worker w0 mid-run "
                         "to demonstrate zero-loss failover")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()

    if cfg.family == "cnn":
        if args.workers > 0:
            serve_supervised(cfg, args)
        else:
            serve_images(cfg, args)
        return

    scfg = ServeConfig(max_batch=args.max_batch, max_len=args.max_len,
                       cross_len=128 if cfg.family == "audio" else 0)
    eng = Engine(cfg, scfg, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(4, min(64, args.max_len - args.max_new)))
        req = Request(prompt=list(rng.integers(1, cfg.vocab_size,
                                               size=plen)),
                      max_new=args.max_new)
        if cfg.family == "audio":
            req.frames = rng.standard_normal(
                (128, cfg.d_model)).astype(np.float32) * 0.1
        if cfg.family == "vlm":
            req.patches = rng.standard_normal(
                (cfg.num_patches, 1024)).astype(np.float32) * 0.1
        reqs.append(req)
        eng.submit(req)

    eng.run_until_done()
    done = sum(r.done for r in reqs)
    print(f"finished {done}/{len(reqs)} requests; "
          f"{eng.tokens_generated} tokens; "
          f"decode throughput {eng.decode_tokens_per_s:.1f} tok/s "
          f"({eng.decode_steps} batched decode steps)")


if __name__ == "__main__":
    main()
