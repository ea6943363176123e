"""The program's side of the net ``chain``: the model config, weights tree
and conv routes that ``CnnEngine`` serves a configuration file with.

With ``bench/serve.py``, the only code of the benchmark that imports the
program; its functions import it when called, from ``serve.py``, which
puts the program's ``src`` on the path.
"""
from __future__ import annotations

import dataclasses


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


def routes(pcfg) -> list:
    """``(layer, datapath)`` of each conv layer, as the program resolves
    it."""
    from repro.models.alexnet import layer_routes

    return layer_routes(pcfg)
