"""Training launcher: ``python -m repro.launch.train --arch smollm-360m ...``

Runs real steps on the available devices (reduced config by default on CPU;
full config with --full on a real fleet).  The production path is identical
to the dry-run's: same step function, same shardings — only array allocation
differs.
"""
from __future__ import annotations

import argparse
import json

import jax

from ..configs import ASSIGNED, get_config
from .mesh import make_host_mesh
from ..parallel import sharding as shlib
from ..runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=ASSIGNED)
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real fleet); default reduced")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--mesh", default="",
                    help="'DxM' data x model mesh over available devices")
    ap.add_argument("--rules", default="", help="JSON logical-rule overrides")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_host_mesh((d, m), ("data", "model"))
    rules = json.loads(args.rules) if args.rules else None

    tcfg = TrainerConfig(steps=args.steps, batch=args.batch,
                         seq_len=args.seq_len, base_lr=args.lr,
                         ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                         log_every=max(args.steps // 20, 1))
    tr = Trainer(cfg, tcfg, mesh=mesh, rules=rules)
    # resume if a checkpoint exists
    if args.ckpt_dir:
        if tr.restore_latest():
            print(f"resumed from step {int(jax.device_get(tr.state['step']))}")
    hist = tr.run()
    for h in hist:
        print(f"step {h['step']:6d} loss {h['loss']:8.4f} "
              f"acc {h['accuracy']:6.3f} gnorm {h['grad_norm']:8.3f} "
              f"dt {h['dt']*1e3:7.1f}ms")
    if tr.events.stragglers:
        print(f"stragglers detected: {len(tr.events.stragglers)}")
    if tr.events.recoveries:
        print(f"failure recoveries: {tr.events.recoveries}")


if __name__ == "__main__":
    main()
