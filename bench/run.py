"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload alexnet.closed --seed 7 --seconds 10 \
        --trace 0

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<config>.json``,
its traffic in ``bench/traffic/<traffic>.json`` (driven by
``bench/traffic/<kind>.py``), and each per-layer metric's reader in
``bench/metrics/<metric>.py``.

One run: find the chips (exit 2 where JAX finds no TPU or too few), make
the weights and images from the seed on the device, build ``CnnEngine``
and warm every bucket (all of that is ``setup_s``), drive the traffic for
``--seconds``, finish what is in flight, read the device's peak memory,
free the engine, and check a seeded sample of the served logits against
the plain reference (``reference.py``).  With ``--trace 1`` the window is
traced and the per-layer metrics are reported instead of the end-to-end
ones.  The last line of standard output is the result's JSON object; the
numbers compared, each beside its limit, are also the last lines of
standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# JAX's persistent compilation cache: a fixed directory in this checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(HERE, "out", "trace")
if HERE not in sys.path:
    sys.path.insert(0, HERE)

NO_CHIP = 2


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def config_file(name: str, root: str = ROOT) -> dict:
    entry = {c["name"]: c for c in benchmark(root)["configs"]}[name]
    return _json(os.path.join(root, entry["file"]))


def traffic_file(name: str) -> dict:
    return _json(os.path.join(HERE, "traffic", f"{name}.json"))


def traffic_kind(kind: str):
    return _load_module(os.path.join(HERE, "traffic", f"{kind}.py"),
                        f"bench_traffic_{kind}")


def metric_reader(name: str):
    return _load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                        f"bench_metric_{name.replace('.', '_')}")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic file
    end_to_end: list        # BENCHMARK.json metric entries of this cell
    per_layer: list


def cell(name: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name, w["chips"], config_file(w["config"], root),
                traffic_file(w["traffic"]), mine(bm["end_to_end"]),
                mine(bm["per_layer"]))


# ---------------------------------------------------------------------------
# small statistics
# ---------------------------------------------------------------------------
def nearest_rank(values, q: float) -> float:
    v = np.sort(np.asarray(values, float))
    return float(v[max(int(np.ceil(q * len(v))) - 1, 0)])


class CompileCounter:
    """Compile requests (persistent-cache hits and misses) and backend
    compiles, counted from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.requests = self.compiles = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def total(self) -> int:
        return self.requests + self.compiles


# ---------------------------------------------------------------------------
# what the metric readers see
# ---------------------------------------------------------------------------
@dataclass
class Measured:
    chips: int
    out: object             # driver.Outcome
    batches: dict           # bucket -> batches retired in the window
    served_in_window: int
    trace: object           # trace.Reduction or None
    layers: list            # work.LayerWork per layer, one image
    image_flops: int
    peaks: dict


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def logit_err(cfg: dict, params, pool, sample: list, precision: str,
              block: int) -> float:
    """Widest gap, over the sampled requests, between a served row and the
    reference's row for its image at ``precision``, relative to the
    reference row's largest magnitude."""
    import reference

    if not sample:
        return float("inf")
    images = sorted({img for img, _, _ in sample})
    where = {img: i for i, img in enumerate(images)}
    ref = reference.logits(cfg, params, pool[images], precision, block)
    return max(float(np.abs(got - ref[where[img]]).max()
                     / np.abs(ref[where[img]]).max())
               for img, got, _ in sample)


@dataclass
class Setup:
    """A cell made ready: weights, images and a warmed engine."""
    dev: dict
    params: dict
    images: np.ndarray
    server: object
    counter: CompileCounter
    marks: dict
    kernel_names: set


def setup(c: Cell, seed: int, *, require_chip: bool = True,
          server_hook=None) -> Setup:
    """Find the chips, make the weights and the image pool from the seed
    and build and warm the engine on the buckets the traffic uses."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    import device
    import reference
    import serve

    cfg, traffic = c.config, c.traffic
    dev = (device.require_tpu(c.chips) if require_chip else device.report())
    print(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})",
          file=sys.stderr)
    from repro.serving.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    counter = CompileCounter()
    marks = {}

    t = time.perf_counter()
    params = reference.make_params(cfg, seed)
    jax.block_until_ready(params)
    marks["params_s"] = time.perf_counter() - t
    t = time.perf_counter()
    images = reference.make_images(cfg, seed, traffic["pool"])
    marks["images_s"] = time.perf_counter() - t
    t = time.perf_counter()
    server = serve.EngineServer(cfg, params)
    if server_hook is not None:
        server_hook(server)
    compile_s = server.warm(traffic.get("buckets"))
    marks["engine_s"] = time.perf_counter() - t
    kernel_names = server.kernel_names()
    print("conv routes: " + " ".join(f"{n}={r}" for n, r in server.routes),
          file=sys.stderr)
    print("bucket seconds (compile or cache load): " + " ".join(
        f"{b}={s:.2f}" for b, s in compile_s.items()), file=sys.stderr)
    print(f"compile requests in set-up: {counter.requests}, backend "
          f"compiles: {counter.compiles}; Pallas kernels: "
          f"{len(kernel_names)}", file=sys.stderr)
    return Setup(dev, params, images, server, counter, marks, kernel_names)


def latencies_s(out) -> list:
    """Open loop: each request's seconds from its due time to its logits on
    the host; one never served counts as infinitely late."""
    return [(r.t_done - (out.t_open + r.due)) if r.served else float("inf")
            for r in out.records]


def run_cell(c: Cell, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, server_hook=None,
             controls=()) -> dict:
    """One run of cell ``c``; returns the result object, with the reading
    of each precision in ``controls`` (``reference.forward``'s names) put
    in the served logits' place under ``"controls"``."""
    import jax

    import device
    import driver
    import trace as tr
    import work

    cfg, traffic = c.config, c.traffic
    s = setup(c, seed, require_chip=require_chip, server_hook=server_hook)
    dev, params, images, server = s.dev, s.params, s.images, s.server
    counter, marks, kernel_names = s.counter, s.marks, s.kernel_names
    del s

    rng = np.random.default_rng(seed)
    sampler = driver.Sampler(cfg["check"]["sample"],
                             np.random.default_rng([seed, 1]))
    span = driver.spans(trace)
    kind = traffic_kind(traffic["kind"])
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    before, compiles0 = server.batches(), counter.total()
    setup_s = time.perf_counter() - T_START
    with span("bench.window"):
        out = kind.drive(server, images, traffic, rng, seconds, sampler,
                         span)
    after, compiles_in = server.batches(), counter.total() - compiles0
    if trace:
        jax.profiler.stop_trace()
        with open(os.path.join(TRACE_DIR, "kernel_names.json"), "w") as f:
            json.dump(sorted(kernel_names), f)
    driver.finish(server, out, sampler, span)
    batches = {b: n - before.get(b, 0) for b, n in after.items()
               if n - before.get(b, 0)}
    engine_report = server.report()
    mem = device.memory_peak_bytes(c.chips)
    server.close()
    del server
    gc.collect()

    recs = out.records
    attempted = len(recs)
    failed = sum(not r.served for r in recs)
    served_in = sum(r.served and r.t_done <= out.t_close for r in recs)
    t = time.perf_counter()
    block = cfg["check"]["block"]
    err = logit_err(cfg, params, images, sampler.items, "highest", block)
    control_errs = {p: logit_err(cfg, params, images, sampler.items, p,
                                 block) for p in controls}
    ref_s = time.perf_counter() - t

    layers = work.layer_work(cfg)
    flops = work.image_flops(cfg)
    direct = 2 * sum(w.direct_macs for w in layers)
    rows = sum(b * n for b, n in batches.items())
    print(f"window {out.seconds:.3f}s: {attempted} sent, {served_in} served "
          f"in it, {failed} never served; batches by bucket {batches}; "
          f"images per batch row {served_in / rows if rows else 0:.4f}; "
          f"{out.steps} steps, {out.step_s:.3f}s in engine.step",
          file=sys.stderr)
    print(f"compiles inside the window: {compiles_in}", file=sys.stderr)
    print(f"direct-equivalent GFLOP/s {served_in * direct / out.seconds / 1e9:.1f}"
          f" (paper: 1382 on Arria 10); least-work GFLOP/s "
          f"{served_in * flops / out.seconds / 1e9:.1f}", file=sys.stderr)
    print(f"set-up: {setup_s:.2f}s ({', '.join(f'{k} {v:.2f}' for k, v in marks.items())}); "
          f"reference check {ref_s:.2f}s over "
          f"{len({i for i, _, _ in sampler.items})} images, "
          f"{len(sampler.items)} requests", file=sys.stderr)
    print(f"engine: {json.dumps(engine_report)}", file=sys.stderr)

    limit = cfg["check"]["logit_err"]
    checks = {
        "logit_err": {"value": err, "limit": limit},
        "never_served": {"value": failed, "limit": 0},
        "compiles_in_window": {"value": compiles_in, "limit": 0},
    }
    correct = all(v["limit"] is not None and v["value"] <= v["limit"]
                  for v in checks.values())

    device_out = dict(dev, memory_peak_bytes=mem)
    metrics = {}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if not trace:
        values = {"setup_s": setup_s,
                  "img_per_s": served_in / out.seconds}
        if out.lateness_s:
            lat = latencies_s(out)
            values["latency_p50_ms"] = nearest_rank(lat, 0.50) * 1e3
            values["latency_p95_ms"] = nearest_rank(lat, 0.95) * 1e3
        for m in c.end_to_end:
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device_out
    else:
        red = tr.reduce(*tr.load(jax.profiler.ProfileData.from_file(
            tr.xplane_path(TRACE_DIR)), c.chips), kernel_names)
        m_in = Measured(c.chips, out, batches,
                        served_in, red, layers, flops,
                        device.peaks(dev["kind"]))
        for m in c.per_layer:
            v = metric_reader(m["name"]).read(m_in)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = dict(device_out, busy_s=red.busy_s,
                                window_s=red.window_s)
        result["breakdown"] = tr.breakdown(red)
        print(f"trace: window {red.window_s:.3f}s busy {red.busy_s:.3f}s "
              f"kernels {red.kernel_s:.3f}s xla {red.xla_s:.3f}s "
              f"idle by host span {json.dumps(red.idle_by_span)}",
              file=sys.stderr)
    if control_errs:
        result["controls"] = control_errs
    result["checks"] = checks
    return result


def print_result(result: dict):
    for name, v in result["checks"].items():
        print(f"check {name}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    c = cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import device

    try:
        result = run_cell(c, args.seed, args.seconds, bool(args.trace))
    except device.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return NO_CHIP
    print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
