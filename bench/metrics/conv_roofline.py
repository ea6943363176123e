"""The conv kernels' share of their roofline, in %: the least time of the
conv layers the window launched (each batch at its bucket's rows, by
``work.py`` and the chip's peaks) / the device time of the Pallas kernel
ops in the trace."""


def read(m):
    if m.trace is None or m.trace.kernel_s <= 0 or not m.batches:
        return None
    pk = m.peaks
    least = sum(n * w.least_seconds(rows, pk["bf16_flops_per_s"],
                                    pk["hbm_bytes_per_s"])
                for rows, n in m.batches.items()
                for w in m.layers if w.op == "conv")
    return 100.0 * least / m.trace.kernel_s
