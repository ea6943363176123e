"""From a profiler trace of the window to busy, idle and kernel time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
two lists of ``(name, start_ns, end_ns)``: the device ops of each chip
used (the ``XLA Ops`` line of ``/device:TPU:<n>``) and the harness's own
host spans (names starting ``bench.``).  ``reduce`` then works on those
lists alone:

* the window is the ``bench.window`` span;
* busy time is the union of the device ops' intervals inside the window,
  averaged over the chips used; idle is the rest;
* an op's name is its HLO instruction's (the TPU trace names an op by its
  whole instruction text, ``%conv2d_direct.3 = f32[...] custom-call(...)``);
  Pallas kernels are the ``tpu_custom_call`` instructions, named in
  ``kernel_names`` (from the compiled buckets) or marked so in the text;
  every other op is XLA's (FC dots, pads, copies, glue);
* each idle stretch is put down to the host span it lies under
  (``bench.engine.step``, ``bench.client.submit``, ``bench.client.wait``),
  or to ``other`` where the host was in none.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
PALLAS = 'custom_call_target="tpu_custom_call"'
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
PREFIX = "bench."


@dataclass
class Reduction:
    window_s: float
    busy_s: float               # mean over chips of the union of op time
    kernel_s: float             # Pallas kernel op time, summed, mean/chip
    xla_s: float                # other op time, summed, mean/chip
    ops: dict = field(default_factory=dict)  # op_name -> s, mean/chip
    idle_by_span: dict = field(default_factory=dict)  # span -> idle s
    idle_in_step_s: float = 0.0     # idle time under bench.engine.step

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def xplane_path(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(event_name: str, kernel_names) -> str:
    """``pallas:<instruction>`` or ``xla:<instruction>`` for a device op."""
    m = INSTRUCTION.match(event_name)
    name = m.group(1) if m else event_name
    pallas = name in kernel_names or PALLAS in event_name
    return ("pallas:" if pallas else "xla:") + name


def load(profile, chips: int):
    """``(device, host)``: device ops per chip used (a list of lists) and
    the harness's host spans, from a ``jax.profiler.ProfileData``."""
    device, host = {}, []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            ops = device.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name.startswith(PREFIX)]
    return [device[k] for k in sorted(device)], host


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the (n, 2) intervals ``iv``."""
    if not len(iv):
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def _clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Length of the intersection of two disjoint sorted interval sets."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            tot += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return tot


def _gaps(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def reduce(device: list, host: list, kernel_names) -> Reduction:
    """The window's busy, kernel, XLA and idle time (see the module)."""
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW} span, found {len(windows)}")
    lo, hi = windows[0]
    spans = {}
    for n, s, e in host:
        if n != WINDOW:
            spans.setdefault(n, []).append((s, e))
    spans = {n: _union(_clip(np.array(v, float), lo, hi))
             for n, v in spans.items()}
    kernel_names = set(kernel_names)
    busy = kern = xla = step_idle = 0.0
    ops, idle = {}, {}
    for chip in device:
        iv = np.array([(s, e) for _, s, e in chip], float).reshape(-1, 2)
        keep = (iv[:, 1] > lo) & (iv[:, 0] < hi)
        names = [op_name(n, kernel_names)
                 for (n, _, _), k in zip(chip, keep) if k]
        iv = np.clip(iv[keep], lo, hi)
        covered = _union(iv)
        busy += float((covered[:, 1] - covered[:, 0]).sum())
        for n, (s, e) in zip(names, iv):
            ops[n] = ops.get(n, 0.0) + (e - s)
            if n.startswith("pallas:"):
                kern += e - s
            else:
                xla += e - s
        gaps = _gaps(covered, lo, hi)
        left = float((gaps[:, 1] - gaps[:, 0]).sum())
        for n, sp in spans.items():
            t = _overlap(gaps, sp)
            idle[n] = idle.get(n, 0.0) + t
            left -= t
            if n == "bench.engine.step":
                step_idle += t
        idle["other"] = idle.get("other", 0.0) + left
    k = max(len(device), 1)
    ns = 1e-9
    return Reduction(
        window_s=(hi - lo) * ns, busy_s=busy * ns / k,
        kernel_s=kern * ns / k, xla_s=xla * ns / k,
        ops={n: t * ns / k for n, t in ops.items()},
        idle_by_span={n.removeprefix(PREFIX): t * ns / k
                      for n, t in idle.items()},
        idle_in_step_s=step_idle * ns / k)


def breakdown(r: Reduction, top: int = 10) -> dict:
    """The device ops that took most time and the idle time by what the
    host was doing, at most ``top`` of each, longest first."""
    ops = sorted(r.ops.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(r.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in idle if t > 0]}
