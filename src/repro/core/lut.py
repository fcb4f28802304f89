"""Precomputed lookup tables for the approximate multipliers, width-indexed.

A (2^n)×(2^n) int32 table fully characterizes any n×n multiplier model. The
LUT is the deployment artifact for the ``approx_lut`` execution mode
(gathers on TPU/CPU) and the ground truth for kernel tests.

Width contract
==============

* Tables are keyed ``"{mult_name}[@{n}]"`` (``@8`` implicit, aliases
  resolved), e.g. ``build_lut("proposed")`` → 256×256,
  ``build_lut("csp_axc1@4")`` → 16×16. Exhaustive tables are built for
  n ≤ MAX_LUT_BITS (8); wider widths raise ``ValueError`` — use the
  ``approx_bitexact`` closed form there.
* Index convention: ``lut[a + 2^(n-1), b + 2^(n-1)] = mult(a, b)`` for
  signed a, b in ``[-2^(n-1), 2^(n-1)-1]``. The table width is recoverable
  from ``lut.shape``, so every consumer below is width-aware.
* Wraparound: :func:`lut_multiply` masks gather indices to n bits, so
  out-of-range ints hit the same wrapped entry the closed form computes —
  LUT == bitexact on *arbitrary* int inputs, not just in-range ones.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

Array = jnp.ndarray

MAX_LUT_BITS = 8  # 2^(2n) entries; beyond 8 bits the table is impractical


def _lut_width(table) -> int:
    """Operand width implied by a table's shape (inverse of build_lut)."""
    size = table.shape[0]
    n = size.bit_length() - 1
    if table.shape[-2:] != (1 << n, 1 << n):
        raise ValueError(f"not a product LUT shape: {table.shape}")
    return n


@functools.lru_cache(maxsize=None)
def _build_lut_canonical(key: str) -> np.ndarray:
    from repro.core import multiplier as m

    base, n = m.split_width(key)
    if n > MAX_LUT_BITS:
        raise ValueError(
            f"exhaustive LUTs are built for widths <= {MAX_LUT_BITS} "
            f"(got {key!r}: 2^{2 * n} entries); use the approx_bitexact "
            "closed form for wider operands")
    fn = m.make_multiplier(base, n)
    with jax.ensure_compile_time_eval():
        lo, hi = -(1 << (n - 1)), 1 << (n - 1)
        v = jnp.arange(lo, hi, dtype=jnp.int32)
        a, b = jnp.meshgrid(v, v, indexing="ij")
        table = fn(a.reshape(-1), b.reshape(-1)).reshape(1 << n, 1 << n)
    return np.asarray(table, dtype=np.int32)


def build_lut(mult_name: str) -> np.ndarray:
    """Build (and cache) the product table for ``"name[@N]"`` (N ≤ 8).

    Runs under ``ensure_compile_time_eval`` so the table stays concrete even
    when first requested inside an outer trace (e.g. lowering a model whose
    ``dot_plan`` consults the LUT). Aliases and the implicit ``@8`` width are
    canonicalized before caching, so ``"proposed"``, ``"proposed@8"`` and a
    spec-derived key share one table.
    """
    from repro.core import multiplier as m

    return _build_lut_canonical(m.canonical_key(mult_name))


def lut_multiply(a: Array, b: Array, lut: Array) -> Array:
    """Gather-based approximate product; width derives from ``lut.shape``.

    Indices are masked to the table's operand width, matching the closed
    form's operand-wraparound semantics for out-of-range ints.
    """
    lut = jnp.asarray(lut)
    n = _lut_width(lut)
    size, off = 1 << n, 1 << (n - 1)
    ai = (jnp.asarray(a, jnp.int32) + off) & (size - 1)
    bi = (jnp.asarray(b, jnp.int32) + off) & (size - 1)
    return lut[ai, bi]


def flat_lut(mult_name: str) -> np.ndarray:
    """Flat ``(2^{2n},)`` view of the product table for gather kernels.

    Entry layout matches the index the LUT Pallas kernel computes:
    ``flat[((a + off) & mask) << n | ((b + off) & mask)] = mult(a, b)``
    with ``off = 2^(n-1)``, ``mask = 2^n - 1`` — i.e. a row-major flatten
    of the 2-D table, so the 2-D and flat gathers hit identical entries.
    """
    return build_lut(mult_name).reshape(-1)


def f00(mult_name: str) -> int:
    """The model's product at (0, 0) — the k-padding correction constant.

    Approximate wirings map (0,0) to a nonzero value (the compensation
    constant fires regardless of operands), and that value differs across
    wirings and widths (e.g. proposed@8 → 192, design_strollo2020@8 → 64,
    proposed@4 → 4): any contraction that zero-pads the k dimension must
    subtract *this wiring's* f(0,0) per padded element, never a hard-coded
    constant. Shared by ``kernels/approx_matmul`` and ``kernels/lut_matmul``.
    """
    table = build_lut(mult_name)
    off = 1 << (_lut_width(table) - 1)
    return int(table[off, off])


def error_lut(mult_name: str) -> np.ndarray:
    """(2^n)×(2^n) table of (approx − exact) — compact error characterization."""
    table = build_lut(mult_name)
    n = _lut_width(table)
    lo, hi = -(1 << (n - 1)), 1 << (n - 1)
    v = np.arange(lo, hi, dtype=np.int64)
    exact = v[:, None] * v[None, :]
    return (table.astype(np.int64) - exact).astype(np.int32)


def error_moments(mult_name: str) -> dict:
    """Mean/std of the error under uniform operands — drives approx_stat mode.

    Normalization is over the table's own 2^(2n) entries (width-aware), not a
    hard-coded 256×256 — a 4-bit LUT's moments average over 256 pairs.
    """
    e = error_lut(mult_name).astype(np.float64)
    return dict(mean=float(e.mean()), std=float(e.std()), max_abs=float(np.abs(e).max()))
