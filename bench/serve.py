"""The system under test, as the harness drives it: ``CnnEngine``.

This is the one module of the benchmark that imports the program.  It
builds the engine for a configuration file, with the harness's weights,
warms every bucket the cell uses, and exposes submit / step / idle and the
counters the per-layer metrics read.  It takes from the program only the
serving entry, its counters and its kernel names.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

_CUSTOM_CALL = re.compile(
    r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"")


class ConfigMismatch(RuntimeError):
    """The program would run another network than the configuration file
    states."""


def program_config(cfg: dict):
    """The program's model config for a configuration file: its registered
    config with the file's sizes, on the file's route.  Fails unless the
    program's layer geometry is the file's layer table."""
    from repro.configs import get_config
    from repro.launch.serve import apply_cnn_route
    from repro.models.alexnet import layer_specs

    convs = [l for l in cfg["layers"] if l["op"] == "conv"]
    fcs = [l for l in cfg["layers"] if l["op"] == "fc"]
    base = get_config(cfg["model"])
    kw = dict(image_size=cfg["image_size"], in_channels=cfg["in_channels"],
              conv_channels=tuple(l["out"] for l in convs),
              fc_dims=tuple(l["out"] for l in fcs),
              num_classes=fcs[-1]["out"], dtype=cfg["dtype"])
    if base.arch == "vgg":
        kw["pool_after"] = tuple(i + 1 for i, l in enumerate(convs)
                                 if l["pool"])
    pcfg = apply_cnn_route(dataclasses.replace(base, **kw), cfg["route"])
    if cfg["lrn"]:
        lrn = cfg["lrn"]
        if (pcfg.lrn_n, pcfg.lrn_k, pcfg.lrn_alpha, pcfg.lrn_beta) != (
                lrn["n"], lrn["k"], lrn["alpha"], lrn["beta"]):
            raise ConfigMismatch(f"LRN constants differ: {pcfg}")
    for layer, spec in zip(convs, layer_specs(pcfg)):
        want = (layer["kernel"], layer["stride"], layer["padding"],
                layer["groups"], layer["relu"], layer["lrn"],
                tuple(layer["pool"]) if layer["pool"] else None)
        got = (spec.kernel, spec.stride, spec.padding, spec.groups,
               spec.relu, spec.fuse_lrn,
               (spec.pool_window, spec.pool_stride) if spec.fuse_pool
               else None)
        if want != got:
            raise ConfigMismatch(f"{layer['name']}: file {want}, "
                                 f"program {got}")
    return pcfg


def program_params(cfg: dict, params: dict) -> dict:
    """The harness's weights in the program's tree: the same arrays."""
    return {l["name"]: {"w": params[l["name"]]["w"],
                        "b": params[l["name"]]["b"]} for l in cfg["layers"]}


class EngineServer:
    """``CnnEngine`` over one configuration file."""

    def __init__(self, cfg: dict, params: dict):
        from repro.models.alexnet import layer_routes
        from repro.serving import CnnEngine, CnnServeConfig, ImageRequest

        self._request = ImageRequest
        self.pcfg = program_config(cfg)
        self.routes = layer_routes(self.pcfg)
        self.engine = CnnEngine(
            self.pcfg, CnnServeConfig(max_batch=cfg["max_batch"],
                                      staging_depth=cfg["staging_depth"]),
            params=program_params(cfg, params))

    def warm(self, buckets=None) -> dict:
        """Serve one full group in each of ``buckets`` (default: the whole
        ladder), which compiles or loads each bucket's forward and runs it
        once; returns the seconds each compile or cache load took."""
        import numpy as np

        eng = self.engine
        hw, c = self.pcfg.image_size, self.pcfg.in_channels
        img = np.zeros((hw, hw, c), np.dtype(self.pcfg.dtype))
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
