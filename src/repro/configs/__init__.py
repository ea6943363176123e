"""Config registry: ``get_config("starcoder2-15b")`` etc.

One module per assigned architecture (+ the paper's own AlexNet).  All
numbers follow the assignment block; deviations are noted inline and in
DESIGN.md §Arch-applicability.
"""
from __future__ import annotations

from importlib import import_module

_MODULES = {
    "mamba2-2.7b": "mamba2_2p7b",
    "starcoder2-15b": "starcoder2_15b",
    "phi4-mini-3.8b": "phi4_mini_3p8b",
    "llama3.2-3b": "llama3p2_3b",
    "smollm-360m": "smollm_360m",
    "jamba-v0.1-52b": "jamba_v0p1_52b",
    "whisper-tiny": "whisper_tiny",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "phi-3-vision-4.2b": "phi3_vision_4p2b",
    "alexnet": "alexnet",
    "vgg16": "vgg16",
    "resnet50": "resnet50",
}

# the paper-side CNNs live outside the assigned-architecture list
CNN_ARCHS = ["alexnet", "vgg16", "resnet50"]
ASSIGNED = [n for n in _MODULES if n not in CNN_ARCHS]


def list_configs():
    return list(_MODULES)


def get_config(name: str):
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {list(_MODULES)}")
    return import_module(f"repro.configs.{_MODULES[name]}").config()
