"""Traffic generators, one module per arrival ``kind``.

Each module has ``schedule(traffic, seconds, rng)``. Every seed gets the same
set of sizes and the same set of gaps, in its own order: the seed changes
which request comes when and what it holds, not how much work a window has.
"""
import numpy as np


def stratified_exponential(n: int, rate: float, rng) -> np.ndarray:
    """``n`` gaps at the exponential's (i + 1/2)/n quantiles, mean ~1/rate,
    in an order drawn from ``rng``."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) / rate)


def exact_shares(n: int, shares, rng) -> np.ndarray:
    """``n`` indices into ``shares`` with counts as close to the shares as
    whole numbers allow, in an order drawn from ``rng``."""
    shares = np.asarray(shares, float) / np.sum(shares)
    counts = np.floor(shares * n).astype(int)
    for i in np.argsort(-(shares * n - counts))[: n - counts.sum()]:
        counts[i] += 1
    return rng.permutation(np.repeat(np.arange(len(shares)), counts))
