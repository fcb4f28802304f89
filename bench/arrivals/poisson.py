"""Open-loop Poisson arrivals of single frames.

Traffic keys: ``rate_per_s``; ``frames``: a list of ``{"height", "width",
"share"}``; ``pool_per_resolution``: distinct frames kept per resolution.
Returns ``[(t_offset_s, resolution_index, pool_index), ...]``.
"""
import numpy as np

from bench.arrivals import exact_shares, stratified_exponential


def schedule(traffic, seconds, rng):
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = stratified_exponential(n, rate, rng)
    times = np.cumsum(gaps)
    res = exact_shares(n, [f["share"] for f in traffic["frames"]], rng)
    pool = rng.integers(0, traffic["pool_per_resolution"], n)
    return [(float(t), int(r), int(p)) for t, r, p in zip(times, res, pool)]
