"""Find the highest open-loop rate a configuration sustains: the knee.

    python3 bench/sweep.py --config alexnet --kind poisson --pool 256 \
        --seconds 6 --rates 1000,1500,2000,2500 --seed 5

One engine, warmed on every bucket, serves each rate in turn for
``--seconds``; one JSON line per rate gives the images sent and served in
the window, the backlog left at its close, and the latency quantiles from
the due times.  The knee is the highest rate whose backlog does not grow
through the window; a cell's rate is fixed from it once, by hand, in its
traffic file.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--kind", default="poisson")
    ap.add_argument("--pool", type=int, default=256)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
    import driver

    base = {"kind": args.kind, "pool": args.pool}
    c = run.Cell("sweep", 1, run.config_file(args.config), base, [], [])
    s = run.setup(c, args.seed)
    kind = run.traffic_kind(args.kind)
    for rate in (float(r) for r in args.rates.split(",")):
        p = dict(base, rate_hz=rate)
        sampler = driver.Sampler(1, np.random.default_rng(0))
        span = driver.spans(False)
        before = s.server.batches()
        out = kind.drive(s.server, s.images, p, np.random.default_rng(
            args.seed), args.seconds, sampler, span)
        backlog = len(out.inflight)
        driver.finish(s.server, out, sampler, span)
        lat = run.latencies_s(out)
        served = sum(r.served and r.t_done <= out.t_close
                     for r in out.records)
        print(json.dumps({
            "rate_hz": rate, "sent": len(out.records),
            "served_in_window_per_s": served / out.seconds,
            "backlog_at_close": backlog,
            "p50_ms": run.nearest_rank(lat, 0.5) * 1e3,
            "p95_ms": run.nearest_rank(lat, 0.95) * 1e3,
            "p99_ms": run.nearest_rank(lat, 0.99) * 1e3,
            "lateness_p95_ms": run.nearest_rank(out.lateness_s, 0.95) * 1e3,
            "batches": {b: n - before.get(b, 0)
                        for b, n in s.server.batches().items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
