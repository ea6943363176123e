"""Open loop, bursts of near-simultaneous images at a mean ``rate_hz``.

Each burst holds ``burst_sizes[i]`` images (the list cycled over the
window), each image ``jitter_s`` at most after its burst's start; bursts
are evenly spaced so that the window carries ``rate_hz * seconds``
images.  Every seed gets the same bursts in another order.  Parameters:
``rate_hz``, ``burst_sizes``, ``jitter_s``, ``pool``.
"""
import numpy as np

from driver import open_loop


def schedule(p: dict, rng, seconds: float) -> np.ndarray:
    n = max(1, int(round(p["rate_hz"] * seconds)))
    sizes, total = [], 0
    while total < n:
        sizes.append(min(p["burst_sizes"][len(sizes) % len(p["burst_sizes"])],
                         n - total))
        total += sizes[-1]
    sizes = rng.permutation(sizes)
    gap = seconds / len(sizes)
    due = [i * gap + rng.uniform(0, p["jitter_s"], s)
           for i, s in enumerate(sizes)]
    return np.minimum(np.sort(np.concatenate(due)), np.nextafter(seconds, 0))


def drive(server, images, p: dict, rng, seconds: float, sampler, span):
    due = schedule(p, rng, seconds)
    order = rng.permutation(len(images))
    return open_loop(server, images, order, due, seconds, sampler, span)
