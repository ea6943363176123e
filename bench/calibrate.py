"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 bench/calibrate.py --workload alexnet.closed --seconds 3 \
        --seeds 101,102,103 --controls bf16_3x

For each seed, one run of the cell in this process (weights, engine,
window, reference check), printing one JSON line: the program's
``logit_err`` and, for each control precision, the same number with the
reference at that precision in the served logits' place.  The runs share
the compilation cache, so only the first seed compiles.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--controls", default="bf16_3x")
    ap.add_argument("--precision", default=None,
                    help="run the program at this default matmul precision "
                         "instead of the configuration's")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
    c = run.cell(args.workload)
    if args.precision:
        c.config["matmul_precision"] = args.precision
    controls = [p for p in args.controls.split(",") if p]
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(c, seed, args.seconds, False, controls=controls)
        print(json.dumps({
            "workload": c.name, "seed": seed,
            "precision": c.config["matmul_precision"],
            "logit_err": res["checks"]["logit_err"]["value"],
            "controls": res.get("controls", {}),
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "failed": res["failed"], "attempted": res["attempted"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
