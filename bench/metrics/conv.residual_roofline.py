"""The residual-fused conv kernels' share of their roofline, in %: the
least time of the conv layers whose Pallas kernel adds a shortcut in its
epilogue (device ops ``pallas:<layer>_direct_add``), each batch of the
window at its bucket's rows (the net's ``work.py``, shortcut bytes
included, and the chip's peaks) / those ops' device time in the trace.
No such op in the trace: no reading."""
import re

FUSED = re.compile(r"^pallas:(.+)_direct_add(?:\.\d+)?$")


def read(m):
    if m.trace is None or not m.batches:
        return None
    layers, kernel_s = set(), 0.0
    for op, seconds in m.trace.ops.items():
        hit = FUSED.match(op)
        if hit:
            layers.add(hit.group(1))
            kernel_s += seconds
    if kernel_s <= 0:
        return None
    pk = m.peaks
    least = sum(n * w.least_seconds(rows, pk["bf16_flops_per_s"],
                                    pk["hbm_bytes_per_s"])
                for rows, n in m.batches.items()
                for w in m.layers if w.op == "conv" and w.name in layers)
    return 100.0 * least / kernel_s
