"""The program's side of the net ``resnet``: the model config, weights
tree and conv routes that ``CnnEngine`` serves a configuration file with.

With ``bench/serve.py``, the only code of the benchmark that imports the
program; its functions import it when called, from ``serve.py``, which
puts the program's ``src`` on the path.
"""
from __future__ import annotations

import dataclasses


class ConfigMismatch(RuntimeError):
    """The program would run another network than the configuration file
    states."""


def _program_table(pcfg) -> list:
    """The program's network as a configuration file's ``layers`` table,
    with each conv's padding as the pads it reads."""
    from repro.models import resnet
    from repro.nn.conv import conv_pads

    table = []
    for l in resnet.layers(pcfg):
        s = l.spec
        table.append({"name": l.name, "op": "conv", "input": l.input,
                      "kernel": s.kernel, "stride": s.stride,
                      "padding": list(conv_pads(l.in_hw, s.kernel, s.stride,
                                                s.padding)),
                      "out": l.c_out, "relu": s.relu, "add": l.add})
        if l.name == "stem":
            window, stride, pads = resnet.POOL
            table.append({"name": "pool", "op": "maxpool", "input": "stem",
                          "window": window, "stride": stride,
                          "padding": list(pads)})
    table.append({"name": "gap", "op": "avgpool", "input": table[-1]["name"]})
    table.append({"name": "fc", "op": "fc", "input": "gap",
                  "out": pcfg.num_classes, "relu": False})
    return table


def program_config(cfg: dict):
    """The program's model config for a configuration file: its registered
    config with the file's sizes, on the file's route.  Fails unless the
    program's layer graph is the file's layer table."""
    from repro.configs import get_config
    from repro.launch.serve import apply_cnn_route

    base = get_config(cfg["model"])
    pcfg = apply_cnn_route(dataclasses.replace(
        base, image_size=cfg["image_size"], in_channels=cfg["in_channels"],
        stem_channels=cfg["stem_channels"],
        stage_blocks=tuple(cfg["stage_blocks"]),
        stage_widths=tuple(cfg["stage_widths"]),
        expansion=cfg["expansion"], num_classes=cfg["layers"][-1]["out"],
        bn_eps=cfg["bn"]["eps"], dtype=cfg["dtype"]), cfg["route"])
    got = _program_table(pcfg)
    if len(got) != len(cfg["layers"]):
        raise ConfigMismatch(f"{len(cfg['layers'])} layers in the file, "
                             f"{len(got)} in the program")
    for want, have in zip(cfg["layers"], got):
        if want != have:
            raise ConfigMismatch(f"file {want}, program {have}")
    return pcfg


def program_params(cfg: dict, params: dict) -> dict:
    """The harness's weights in the program's tree: the same arrays, batch
    norm unfolded (the program folds it)."""
    out = {}
    for l in cfg["layers"]:
        if l["op"] == "conv":
            p = params[l["name"]]
            out[l["name"]] = {"w": p["w"], "bn": dict(p["bn"])}
        elif l["op"] == "fc":
            out[l["name"]] = {"w": params[l["name"]]["w"],
                              "b": params[l["name"]]["b"]}
    return out


def routes(pcfg) -> list:
    """``(layer, datapath)`` of each conv layer, as the program resolves
    it."""
    from repro.models.resnet import layer_routes

    return layer_routes(pcfg)
