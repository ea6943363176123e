"""The device the run is on: the chip check, its report and its peaks."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def report() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    dev = report()
    if dev["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {dev['platform']!r})")
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{dev['count']}")
    return dev


def peaks(kind: str) -> dict:
    """The peaks of ``kind`` from ``peaks.json``; an unknown device is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: "
                       f"{sorted(table)}")
    return table[kind]


def memory_peak_bytes(n: int = 1):
    """The peak bytes in use on the fullest of the first ``n`` devices,
    where the backend reports it."""
    import jax

    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()[:n]]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None
