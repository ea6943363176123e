"""The system under test, as the harness drives it: ``CnnEngine``.

With each net's ``bench/nets/<net>/program.py``, the only code of the
benchmark that imports the program, and it puts the program's ``src`` on
the path for them.  It builds the engine for a configuration file, with
the harness's weights in the tree that the net's ``program.py`` gives,
warms every bucket the cell uses, and exposes submit / step / idle and the
counters the per-layer metrics read.  It takes from the program only the
serving entry, its counters and its kernel names.
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

_CUSTOM_CALL = re.compile(
    r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


class EngineServer:
    """``CnnEngine`` over one configuration file, built through its net's
    ``program`` module (``program_config``, ``program_params``,
    ``routes``)."""

    def __init__(self, cfg: dict, params: dict, program):
        from repro.serving import CnnEngine, CnnServeConfig, ImageRequest

        self._request = ImageRequest
        self._image = ((cfg["image_size"], cfg["image_size"],
                        cfg["in_channels"]), cfg["dtype"])
        pcfg = program.program_config(cfg)
        self.routes = program.routes(pcfg)
        self.engine = CnnEngine(
            pcfg, CnnServeConfig(max_batch=cfg["max_batch"],
                                 staging_depth=cfg["staging_depth"]),
            params=program.program_params(cfg, params))

    def warm(self, buckets=None) -> dict:
        """Serve one full group in each of ``buckets`` (default: the whole
        ladder), which compiles or loads each bucket's forward and runs it
        once; returns the seconds each compile or cache load took."""
        import numpy as np

        eng = self.engine
        shape, dtype = self._image
        img = np.zeros(shape, np.dtype(dtype))
        for b in buckets or eng.buckets:
            for _ in range(b):
                eng.submit(self._request(image=img))
            eng.run_until_done()
        eng.reset_metrics()
        return dict(eng.compile_seconds)

    # -- the serving entry --------------------------------------------
    def submit(self, image):
        req = self._request(image=image)
        self.engine.submit(req)
        return req

    def step(self):
        self.engine.step()

    def idle(self) -> bool:
        return self.engine.drained

    # -- what the metrics read ----------------------------------------
    def batches(self) -> dict:
        """Batches retired so far, by bucket size."""
        return dict(self.engine.bucket_counts)

    def kernel_names(self) -> set:
        """Names of the Pallas kernels (``tpu_custom_call``) in every
        compiled bucket, as the profiler names their device ops."""
        names = set()
        for exe in self.engine.executables.values():
            names.update(_CUSTOM_CALL.findall(exe.as_text()))
        return names

    def report(self) -> dict:
        s = self.engine.stats()
        return {"routes": dict(self.routes),
                "bucket_counts": s["bucket_counts"],
                "avg_occupancy": s["avg_occupancy"],
                "batches_failed": s["batches_failed"],
                "degradations": s["degradations"],
                "tuned_layers": s["tuned_layers"],
                "health": s["health"]["state"]}

    def close(self):
        """Drop the engine, its slabs and its compiled buckets."""
        self.engine = None
