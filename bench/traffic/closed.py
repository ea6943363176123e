"""Closed loop: ``clients`` clients with no think time.

Parameters: ``clients``, ``pool`` (distinct seeded images, sent in a
seeded order, cycled).
"""
from driver import closed_loop


def drive(server, images, p: dict, rng, seconds: float, sampler, span):
    order = rng.permutation(len(images))
    return closed_loop(server, images, order, p["clients"], seconds,
                       sampler, span)
