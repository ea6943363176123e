"""Open loop, Poisson arrivals of single images at ``rate_hz``.

Every seed gets the same arrivals in another order: the gaps are the
``rate_hz * seconds`` quantiles of the exponential distribution, shuffled
by the seed and scaled to fill the window, so runs differ in where the
bursts fall and not in how much work they bring.  Parameters:
``rate_hz``, ``pool``.
"""
import numpy as np

from driver import open_loop


def schedule(p: dict, rng, seconds: float) -> np.ndarray:
    """Due times (seconds after the window opens), sorted, under
    ``seconds``."""
    n = max(1, int(round(p["rate_hz"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / p["rate_hz"]
    gaps = rng.permutation(gaps) * (seconds / gaps.sum())
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def drive(server, images, p: dict, rng, seconds: float, sampler, span):
    due = schedule(p, rng, seconds)
    order = rng.permutation(len(images))
    return open_loop(server, images, order, due, seconds, sampler, span)
