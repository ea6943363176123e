"""The whole forward's share of the chip's bf16 peak, in %: images served
in the window x the least operations per image (``work.py``) / (window x
peak).  Float32 at the highest precision takes several bf16 passes, so it
reads well under the peak."""


def read(m):
    if not m.served_in_window:
        return None
    flops = m.served_in_window * m.image_flops
    return 100.0 * flops / (m.out.seconds * m.peaks["bf16_flops_per_s"]
                            * m.chips)
