"""Operations and bytes of one LM decode step's projections, from shapes.

Each ``dense()`` site is an (rows, K) @ (K, N) contraction counted by
``bench.work.matmul``: int8 operands, the weights read once a step, int32
results. Only the real rows count (one a request), not padding.
"""
from __future__ import annotations

from bench import work


def sites(cfg) -> list:
    """(K, N) of every projection a decode step runs, layers and head."""
    d, f = cfg.d_model, cfg.d_ff
    q, kv = cfg.n_heads * cfg.dh, cfg.n_kv_heads * cfg.dh
    layer = [(d, q), (d, kv), (d, kv), (q, d), (d, f), (f, d)]
    return layer * cfg.n_layers + [(d, cfg.vocab)]


def step(cfg, rows: int) -> tuple[float, float]:
    """(ops, bytes) of one decode step over ``rows`` requests."""
    parts = [work.matmul(rows, k, n) for k, n in sites(cfg)]
    return sum(o for o, _ in parts), sum(b for _, b in parts)
