"""Fused layer pipeline: measured wall-clock + modeled HBM bytes per layer,
with the §3.5 weight-prefetch on/off comparison.

The paper's headline argument (§3.5, Table 3) is that running conv, ReLU,
LRN, and pool on-chip keeps feature maps out of external memory between
layers, *and* that filter prefetch hides the weight stream behind compute
("filters for the next convolution layer are prefetched while the current
layer is computed").  This benchmark runs every AlexNet conv layer three
ways —

  unfused:        dispatch_conv (conv+bias+ReLU) -> lrn -> maxpool
                  (full-resolution feature map round-trips HBM up to 3x)
  fused+prefetch: one dispatch_conv with the layer-level ConvSpec; the
                  kernels' manual-DMA 2-slot weight stream double-buffers
                  every filter fetch under MXU compute
  fused-prefetch: same kernels with the DMA run synchronously at each
                  weight-tile transition (bit-equal output, every fetch
                  exposed)

— and emits measured wall-clock next to the modeled HBM traffic
(``core/winograd.py::conv2d_hbm_bytes``, route-aware) including the
prefetch split: total weight stream, exposed vs prefetch-hidden bytes, and
the per-layer roofline terms (``core/roofline.py::conv_layer_roofline``,
arithmetic intensity over total and over exposed bytes).

``--batch-block`` / ``--k-block`` set the filter-cache depth and K block
for both the kernels and the model; the defaults (2 cache generations at
batch 4, K split into several tiles per layer) put *every* layer in the
steady-state streaming regime — >= 2 weight fetches, the re-fetches being
exactly what the prefetch hides — so the on/off exposure delta is strict
on all five layers.  (A single-tile stream is fetched once and kept
resident; both modes then expose the same warmup tile.)

    PYTHONPATH=src python benchmarks/fused_pipeline.py [--full]
        [--route {auto,direct,winograd,pallas}] [--prefetch {on,off}]
        [--batch N] [--batch-block N] [--k-block N] [--check]
        [--image-size N] [--out BENCH_fused_pipeline.json]
        [--autotune] [--autotune-budget N]

``--autotune`` additionally runs the measured per-layer autotuner
(``core/autotune.py``) over the same config — enumerating the real launch
knobs, timing each candidate through dispatch_conv, and reporting
default-vs-tuned wall-clock per layer (the ``autotune`` artifact
section).

``--check`` exits nonzero unless (a) every Pallas-resolved layer models
fused bytes strictly below unfused and no layer models fused above
unfused, and (b) modeled prefetch-exposed weight bytes are <= the
non-prefetch weight bytes on every layer — strictly below whenever the
layer has more than one weight fetch (the CI bench-smoke gate).
"""
import argparse
import dataclasses
import json
import sys

import jax
import numpy as np

try:                      # package use (benchmarks.run)
    from .common import emit, time_us
except ImportError:       # direct `python benchmarks/fused_pipeline.py` (CI)
    from common import emit, time_us

import jax.numpy as jnp                                    # noqa: E402
from repro.core.roofline import (ConvLayerRoofline,        # noqa: E402
                                 conv_layer_roofline, network_conv_roofline)
from repro.core.winograd import conv2d_hbm_bytes, conv_flops  # noqa: E402
from repro.launch.serve import CNN_ROUTES, apply_cnn_route  # noqa: E402
from repro.models import alexnet                           # noqa: E402
from repro.nn import pooling                               # noqa: E402
from repro.nn.conv import (MODEL_ROUTES, dispatch_conv,  # noqa: E402
                           resolve_kernel)


def _layer_model(spec, batch, h, c_in, c_out, kernel_name, *,
                 k_block: int = 128, batch_block: int = 8,
                 weight_prefetch: bool = True):
    route, wino = MODEL_ROUTES[kernel_name]
    return conv2d_hbm_bytes(
        batch, h, h, c_in, c_out, spec.kernel,
        spec.winograd_m if wino else None, stride=spec.stride,
        padding=spec.padding, relu=spec.relu, fuse_lrn=spec.fuse_lrn,
        fuse_pool=spec.fuse_pool, pool_window=spec.pool_window,
        pool_stride=spec.pool_stride, groups=spec.groups, route=route,
        k_block=k_block, batch_block=batch_block,
        weight_prefetch=weight_prefetch)


def _layer_flops(spec, batch, h, c_in, c_out, kernel_name) -> float:
    """2 * MACs on the layer's actual datapath (Winograd-domain mults on
    the Winograd kernels, direct mults elsewhere), batch included."""
    _, wino = MODEL_ROUTES[kernel_name]
    # conv output extent (pre-pool)
    from repro.nn.conv import conv_out_hw
    oh = conv_out_hw(h, spec.kernel, spec.stride, spec.padding)
    direct, wmad = conv_flops(oh, oh, c_in // spec.groups, c_out // spec.groups,
                              spec.kernel, spec.winograd_m if wino else None)
    madds = (wmad if wino else direct) * spec.groups
    return 2.0 * madds * batch


def _pr3_model(spec, batch, h, c_in, c_out):
    """The PR-3 modeling rules, for the network-ratio comparison: pallas
    silently fell back to lax off the 3x3 stride-1 path, the lax route was
    (optimistically) credited with fusion, and bias/ReLU was not counted as
    an unfused stage pass."""
    eligible = spec.winograd_eligible
    hb = conv2d_hbm_bytes(
        batch, h, h, c_in, c_out, spec.kernel,
        spec.winograd_m if eligible else None, stride=spec.stride,
        padding=spec.padding, relu=False, fuse_lrn=spec.fuse_lrn,
        fuse_pool=spec.fuse_pool, pool_window=spec.pool_window,
        pool_stride=spec.pool_stride, groups=spec.groups,
        route="pallas" if eligible else "direct", c_block=128)
    return {"unfused": hb["layer_unfused_bytes"],
            "fused": hb["stream_unfused_bytes"] + hb["final_out_bytes"]}


def layer_rows(cfg, *, batch: int, batch_block: int, k_block: int,
               prefetch: bool, seed: int = 0):
    """Per-layer fused vs unfused and prefetch on vs off: wall-clock
    (measured) + HBM bytes incl. the weight-stream split (model)."""
    rng = np.random.default_rng(seed)
    route = alexnet._route(cfg)
    rows = []
    h, c_in = cfg.image_size, cfg.in_channels
    for i, (spec, c_out) in enumerate(zip(alexnet.layer_specs(cfg),
                                          cfg.conv_channels)):
        spec = spec.with_route(route)
        unfused = dataclasses.replace(spec, fuse_lrn=False, fuse_pool=False)
        x = jnp.asarray(rng.standard_normal((batch, h, h, c_in)), jnp.float32)
        w = jnp.asarray(rng.standard_normal(
            (spec.kernel, spec.kernel, c_in // spec.groups, c_out))
            * (spec.kernel ** -2), jnp.float32)
        b = jnp.asarray(rng.standard_normal((c_out,)), jnp.float32)

        def run_unfused(x, w, b, spec=spec, unfused=unfused):
            # same prefetch mode as the headline fused measurement, so the
            # fused-vs-unfused wall-clock delta never mixes weight-stream
            # modes within one artifact
            return pooling.apply_epilogue(
                dispatch_conv(unfused, x, w, b, weight_prefetch=prefetch,
                              k_block=k_block, batch_block=batch_block),
                spec.lrn if spec.fuse_lrn else None,
                (spec.pool_window, spec.pool_stride) if spec.fuse_pool
                else None)

        def run_fused(x, w, b, spec=spec, pf=True):
            return dispatch_conv(spec, x, w, b, weight_prefetch=pf,
                                 k_block=k_block, batch_block=batch_block)

        t_un = time_us(jax.jit(run_unfused), x, w, b)
        t_fu_on = time_us(jax.jit(lambda x, w, b: run_fused(x, w, b)),
                          x, w, b)
        t_fu_off = time_us(jax.jit(lambda x, w, b: run_fused(x, w, b,
                                                             pf=False)),
                           x, w, b)
        t_fu = t_fu_on if prefetch else t_fu_off
        kernel_name = resolve_kernel(spec, in_hw=h)
        hb = _layer_model(spec, batch, h, c_in, c_out, kernel_name,
                          k_block=k_block, batch_block=batch_block,
                          weight_prefetch=prefetch)
        flops = _layer_flops(spec, batch, h, c_in, c_out, kernel_name)
        rl = conv_layer_roofline(f"conv{i+1}", hb, flops=flops,
                                 weight_prefetch=prefetch)
        pr3 = _pr3_model(spec, batch, h, c_in, c_out)
        rows.append({
            "layer": f"conv{i+1}",
            "route": kernel_name,
            "in_hw": h, "c_in": c_in, "c_out": c_out,
            "fuse_lrn": spec.fuse_lrn, "fuse_pool": spec.fuse_pool,
            "unfused_us": t_un, "fused_us": t_fu,
            "fused_us_prefetch": t_fu_on, "fused_us_noprefetch": t_fu_off,
            "unfused_hbm_bytes": hb["layer_unfused_bytes"],
            "fused_hbm_bytes": hb["layer_fused_bytes"],
            "unfused_direct_hbm_bytes": hb["layer_unfused_direct_bytes"],
            "hbm_savings": hb["fused_savings"],
            "weight_hbm_bytes": hb["weight_hbm_bytes"],
            "weight_tile_bytes": hb["weight_tile_bytes"],
            "weight_fetches": hb["weight_fetches"],
            "weight_exposed_prefetch_bytes":
                hb["weight_exposed_prefetch_bytes"],
            "weight_exposed_noprefetch_bytes":
                hb["weight_exposed_noprefetch_bytes"],
            "weight_hidden_bytes": hb["weight_hbm_hidden_bytes"],
            "filter_cache_reuse": hb["filter_cache_reuse"],
            "flops": flops,
            "ai_total": rl.ai_total, "ai_exposed": rl.ai_exposed,
            "roofline_bound": rl.bound,
            "pr3_unfused_hbm_bytes": pr3["unfused"],
            "pr3_fused_hbm_bytes": pr3["fused"],
        })
        h, c_in = spec.out_hw(h), c_out
    return rows


def network_summary(rows, *, prefetch: bool) -> dict:
    """Whole-network modeled-bytes ratio (fused-pallas vs unfused-direct,
    next to the PR-3-rule value) plus the weight-stream aggregate and the
    network roofline over exposed bytes."""
    fused = sum(r["fused_hbm_bytes"] for r in rows)
    unfused_direct = sum(r["unfused_direct_hbm_bytes"] for r in rows)
    pr3_f = sum(r["pr3_fused_hbm_bytes"] for r in rows)
    pr3_u = sum(r["pr3_unfused_hbm_bytes"] for r in rows)
    exp_on = sum(r["weight_exposed_prefetch_bytes"] for r in rows)
    exp_off = sum(r["weight_exposed_noprefetch_bytes"] for r in rows)
    mode = "prefetch" if prefetch else "noprefetch"
    rl = network_conv_roofline([
        ConvLayerRoofline(
            name=r["layer"], flops=r["flops"],
            feature_bytes=r["fused_hbm_bytes"],
            weight_bytes=r["weight_hbm_bytes"],
            weight_exposed_bytes=r[f"weight_exposed_{mode}_bytes"],
            weight_prefetch=prefetch) for r in rows])
    return {
        "fused_hbm_bytes": fused,
        "unfused_direct_hbm_bytes": unfused_direct,
        "ratio": unfused_direct / fused,
        "pr3_rule_ratio": pr3_u / pr3_f,
        "weight_hbm_bytes": sum(r["weight_hbm_bytes"] for r in rows),
        "weight_exposed_prefetch_bytes": exp_on,
        "weight_exposed_noprefetch_bytes": exp_off,
        "prefetch_exposure_ratio": exp_off / exp_on if exp_on else 0.0,
        "fused_us_prefetch": sum(r["fused_us_prefetch"] for r in rows),
        "fused_us_noprefetch": sum(r["fused_us_noprefetch"] for r in rows),
        "roofline": rl,
    }


def check_rows(rows) -> list:
    """Layers violating the gates: a Pallas-resolved layer must model fused
    strictly below unfused and no layer may model fused above unfused; the
    prefetch-exposed weight bytes must be <= the non-prefetch bytes, and
    strictly below whenever the layer re-fetches (weight_fetches > 1)."""
    bad = []
    for r in rows:
        exp_on = r["weight_exposed_prefetch_bytes"]
        exp_off = r["weight_exposed_noprefetch_bytes"]
        if r["route"].startswith("pallas"):
            if not r["fused_hbm_bytes"] < r["unfused_hbm_bytes"]:
                bad.append(r)
            elif exp_on > exp_off:
                bad.append(r)
            elif r["weight_fetches"] > 1 and not exp_on < exp_off:
                bad.append(r)
        elif r["fused_hbm_bytes"] > r["unfused_hbm_bytes"]:
            bad.append(r)
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full 227px AlexNet (default: reduced config)")
    ap.add_argument("--route", default="auto", choices=CNN_ROUTES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--batch-block", type=int, default=2,
                    help="filter-cache depth for kernels AND model (the "
                         "default gives 2 cache generations at batch 4)")
    ap.add_argument("--k-block", type=int, default=8,
                    help="K block for kernels AND model; the default "
                         "splits every reduced layer's K into several "
                         "tiles, so all five layers exercise the "
                         "steady-state streaming regime the prefetch "
                         "hides (single-tile streams are fetched once "
                         "and exposed equally in both modes)")
    ap.add_argument("--prefetch", default="on", choices=("on", "off"),
                    help="primary weight-stream mode (both are always "
                         "measured and modeled; this picks the headline "
                         "fused_us / exposed-bytes columns)")
    ap.add_argument("--image-size", type=int, default=None,
                    help="override the input image size (reduced default "
                         "131, so the late layers keep non-degenerate "
                         "feature maps)")
    ap.add_argument("--out", default="BENCH_fused_pipeline.json")
    ap.add_argument("--autotune", action="store_true",
                    help="also run the measured per-layer autotuner over "
                         "this config and report default-vs-tuned "
                         "wall-clock (core/autotune.py)")
    ap.add_argument("--autotune-budget", type=int, default=8,
                    help="max measured candidates per layer for --autotune")
    ap.add_argument("--check", action="store_true",
                    help="exit 1 unless every pallas layer models strictly "
                         "lower fused HBM bytes than unfused AND prefetch-"
                         "exposed weight bytes <= (strict when re-fetching) "
                         "non-prefetch weight bytes")
    args = ap.parse_args(argv)

    cfg = alexnet.AlexNetConfig()
    if not args.full:
        # reduced channels but a 131px input: the stock 67px reduction
        # shrinks conv3-5 to 3x3 maps where tile padding swamps the model
        cfg = dataclasses.replace(cfg.reduced(), image_size=131)
    if args.image_size:
        cfg = dataclasses.replace(cfg, image_size=args.image_size)
    cfg = apply_cnn_route(cfg, args.route)
    prefetch = args.prefetch == "on"
    cfg = dataclasses.replace(cfg, weight_prefetch=prefetch)

    rows = layer_rows(cfg, batch=args.batch, batch_block=args.batch_block,
                      k_block=args.k_block, prefetch=prefetch)
    tune = None
    if args.autotune:
        from repro.core.autotune import autotune_alexnet
        tune = autotune_alexnet(cfg, args.batch,
                                max_candidates=args.autotune_budget)
    net = network_summary(rows, prefetch=prefetch)
    emit([{"name": f"fused_pipeline/{r['layer']}",
           "us_per_call": r["fused_us"],
           "derived": (f"route={r['route']};unfused_us={r['unfused_us']:.0f}"
                       f";unfused_MB={r['unfused_hbm_bytes']/2**20:.2f}"
                       f";fused_MB={r['fused_hbm_bytes']/2**20:.2f}"
                       f";hbm_savings={r['hbm_savings']:.2f}x"
                       f";filter_cache={r['filter_cache_reuse']:.0f}x"
                       f";w_exposed_on_KB="
                       f"{r['weight_exposed_prefetch_bytes']/2**10:.1f}"
                       f";w_exposed_off_KB="
                       f"{r['weight_exposed_noprefetch_bytes']/2**10:.1f}"
                       f";ai_exposed={r['ai_exposed']:.0f}"
                       f";bound={r['roofline_bound']}")}
          for r in rows])
    emit([{"name": "fused_pipeline/network", "us_per_call": 0,
           "derived": (f"fused_MB={net['fused_hbm_bytes']/2**20:.2f}"
                       f";unfused_direct_MB="
                       f"{net['unfused_direct_hbm_bytes']/2**20:.2f}"
                       f";ratio={net['ratio']:.2f}x"
                       f";pr3_rule_ratio={net['pr3_rule_ratio']:.2f}x"
                       f";w_exposed_on_KB="
                       f"{net['weight_exposed_prefetch_bytes']/2**10:.1f}"
                       f";w_exposed_off_KB="
                       f"{net['weight_exposed_noprefetch_bytes']/2**10:.1f}"
                       f";prefetch_exposure="
                       f"{net['prefetch_exposure_ratio']:.1f}x"
                       f";us_on={net['fused_us_prefetch']:.0f}"
                       f";us_off={net['fused_us_noprefetch']:.0f}")}])
    if tune is not None:
        emit([{"name": f"fused_pipeline/autotune/{t['layer']}",
               "us_per_call": t["tuned_us"],
               "derived": (f"default_us={t['default_us']:.0f}"
                           f";speedup={t['default_us']/t['tuned_us']:.2f}x"
                           f";candidates={t['candidates']}"
                           f";plan={t['plan']}")}
              for t in tune])

    artifact = {
        "config": dataclasses.asdict(cfg),
        "batch": args.batch,
        "batch_block": args.batch_block,
        "k_block": args.k_block,
        "route": args.route,
        "prefetch": args.prefetch,
        "backend": jax.default_backend(),
        "layers": rows,
        "network": net,
    }
    if tune is not None:
        artifact["autotune"] = tune
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)

    if args.check:
        bad = check_rows(rows)
        if bad:
            print(f"fused_pipeline/CHECK_FAILED,0,"
                  f"layers={[r['layer'] for r in bad]}")
            return 1
        print("fused_pipeline/CHECK_OK,0,"
              "fused<unfused_and_prefetch_exposed<=noprefetch_all_layers")
    return 0


if __name__ == "__main__":
    sys.exit(main())
