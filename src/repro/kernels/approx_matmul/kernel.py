"""Tiled approximate int8 matmul Pallas kernel (vectorized k-slab).

TPU adaptation of the paper's MAC array: every scalar product is an
approximate-multiplier closed form (VPU integer ops); accumulation is exact
int32 (the paper's adder tree is exact). The product model is pluggable
(``product_fn``): the default is the proposed 8-bit design's hand-derived
closed form, and ``kernels.closed_form.make_closed_form`` generates the
same algebra for every other CSP wiring/width.

Tiling: grid (M/bm, N/bn, K/bk); the output block (bm, bn) is revisited
across the k dimension (TPU sequential grid) and accumulated in place. The
inner k-slab is walked in ``k_chunk``-wide vectorized slabs: each step
broadcasts a (bm, kc, 1) slice of A against a (1, kc, bn) slice of B and
reduces the kc axis — one whole-slab VPU evaluation instead of a per-k
rank-1 update (recoverable with ``k_chunk=1``, which benchmarks keep as the
baseline). The slab walk is a Python loop with static offsets: Mosaic
lowers static value slices, but neither ``dynamic_slice`` on loaded values
nor a dynamic ref slice that is not a multiple of 128 lanes. The (bm, kc,
bn) int32 working set bounds VMEM: 512 KiB at the default 128×8×128 — a
full 128-deep slab would need 8 MiB, which is why the chunk walk exists.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocking
from repro.kernels.closed_form import approx_product_i32


def resolve_k_chunk(k_chunk: int, block_k: int) -> int:
    """Largest divisor of ``block_k`` not exceeding ``k_chunk`` (≥ 1)."""
    return max(1, math.gcd(int(k_chunk), int(block_k)))


def _matmul_kernel(a_ref, b_ref, o_ref, *, k_chunk: int, product_fn):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...].astype(jnp.int32)  # (bm, bk)
    b = b_ref[...].astype(jnp.int32)  # (bk, bn)
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k0 in range(0, a.shape[1], k_chunk):
        a_s = a[:, k0:k0 + k_chunk]  # (bm, kc)
        b_s = b[k0:k0 + k_chunk, :]  # (kc, bn)
        prod = product_fn(a_s[:, :, None], b_s[None, :, :])  # (bm, kc, bn)
        acc = acc + prod.sum(axis=1)
    o_ref[...] += acc


def approx_matmul_pallas(a, b, *, product_fn=approx_product_i32,
                         block_m: int = 128, block_n: int = 128,
                         block_k: int = 128, k_chunk: int = 8,
                         interpret: bool = False,
                         name: str = "approx_matmul"):
    """(M,K) @ (K,N) int-domain contraction under ``product_fn``.

    a: (M, K) int32 operands in the model's domain; b: (K, N) int32.
    Returns (M, N) int32. ``k_chunk`` is clamped to a divisor of the block
    (``k_chunk=1`` reproduces the historical scalar k-walk). All dims must
    be multiples of their block sizes — non-multiples raise instead of
    silently computing garbage (``ops.approx_matmul`` pads arbitrary
    shapes and corrects for the multiplier's f(0,0) padding artifact).
    """
    m, k = a.shape
    _, n = b.shape
    blocking.check_kernel_shapes(
        "approx_matmul_pallas", "kernels.approx_matmul.ops.approx_matmul",
        a.shape, b.shape, block_m, block_n, block_k)
    k_chunk = resolve_k_chunk(k_chunk, block_k)
    grid = (m // block_m, n // block_n, k // block_k)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_chunk=k_chunk,
                          product_fn=product_fn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name=name,
    )(a, b)
