"""Tiled LUT-input approximate matmul Pallas kernel (any wiring, N ≤ 8).

Width- and wiring-generic sibling of ``kernels/approx_matmul``: instead of
a closed form, the scalar product is read from the wiring's product table
(``core.lut.flat_lut``), so every wiring in
``core.multiplier.ALL_MULTIPLIERS`` — and every enumerable width 3..8 —
runs on the same kernel. (Since the closed-form generator landed, the LUT
kernel is the *fallback* path: ``PallasSubstrate`` prefers the generated
VPU kernel and keeps this one for product models with no CSP structure.)
Operands index the table as

    row = (a + 2^(N-1)) & (2^N - 1),   col = (b + 2^(N-1)) & (2^N - 1)

which both biases the signed operands into table rows/cols and wraps
out-of-range ints to their low-N-bits value — the same operand-wraparound
semantics the closed form and the 2-D LUT gather implement.

The lookup is gather-free, because Mosaic only gathers within one vreg:
each table entry T[row, col] is selected by two one-hot matmuls on the
MXU. ``onehot(row) @ T`` picks the table rows of a k column of A, and
multiplying those by ``onehot(col)`` of the matching B row picks the
entries; the kc products of a k-slab are summed by the second matmul's
contraction. The 2N-bit products do not fit int8, so the table rides
along split into a high byte and a biased low byte (two (S, S) int8
arrays, S = 2^N padded to 128 lanes; 128 KiB together at N=8): every
matmul is int8 × int8 → int32, selecting single entries, hence exact.

Tiling matches ``approx_matmul``: grid (M/bm, N/bn, K/bk); the (bm, bn)
output block is revisited across the k dimension (TPU sequential grid)
and accumulated in place; the inner k-slab is walked in ``k_chunk``-wide
slabs with static offsets (``k_chunk=1`` recovers a per-k walk).
Interpret mode runs the identical kernel body off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocking
from repro.kernels.approx_matmul.kernel import resolve_k_chunk


def table_width(size: int) -> int:
    """Operand width N implied by a flat table length 2^(2N)."""
    n = (max(int(size), 1).bit_length() - 1) // 2
    if (1 << (2 * n)) != size:
        raise ValueError(
            f"not a flat product-LUT length: {size} (expected 2^(2N) for an "
            "operand width N; build it with core.lut.flat_lut)")
    return n


def _onehot(idx, size: int, axis: int):
    """int8 one-hot of ``idx`` (broadcast along ``axis``) over ``size``."""
    shape = list(idx.shape)
    shape[axis] = size
    hot = jax.lax.broadcasted_iota(jnp.int32, tuple(shape), axis) == idx
    return jnp.where(hot, 1, 0).astype(jnp.int8)


def _dot_i32(x, y):
    return jax.lax.dot(x, y, preferred_element_type=jnp.int32)


def _lut_matmul_kernel(a_ref, b_ref, hi_ref, lo_ref, o_ref, *, k_chunk: int,
                       n_bits: int):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    mask = (1 << n_bits) - 1
    off = 1 << (n_bits - 1)
    a = (a_ref[...].astype(jnp.int32) + off) & mask  # (bm, bk) table rows
    b = (b_ref[...].astype(jnp.int32) + off) & mask  # (bk, bn) table cols
    t_hi, t_lo = hi_ref[...], lo_ref[...]            # (S, S) int8 each
    size = t_hi.shape[0]
    acc = jnp.zeros(o_ref.shape, jnp.int32)
    for k0 in range(0, a.shape[1], k_chunk):
        oa = [_onehot(a[:, k:k + 1], size, 1) for k in range(k0, k0 + k_chunk)]
        rows_hi = jnp.concatenate(
            [_dot_i32(o, t_hi).astype(jnp.int8) for o in oa], axis=1)
        rows_lo = jnp.concatenate(
            [_dot_i32(o, t_lo).astype(jnp.int8) for o in oa], axis=1)
        ob = jnp.concatenate(   # (kc·S, bn): one-hot table cols of the slab
            [_onehot(b[k:k + 1, :], size, 0) for k in range(k0, k0 + k_chunk)],
            axis=0)
        acc = acc + (_dot_i32(rows_hi, ob) << 8) + _dot_i32(rows_lo, ob)
    # each selected low byte was stored biased by -128
    o_ref[...] += acc + 128 * a.shape[1]


def _split_table(table, n_bits: int):
    """Flat (2^{2N},) int32 table → (hi, lo) int8 (S, S) byte planes.

    T = 256·hi + lo + 128, S = max(2^N, 128) (rows/cols beyond 2^N are
    zero padding that no index reaches).
    """
    size = 1 << n_bits
    pad = max(size, blocking.LANE) - size
    t = jnp.pad(table.reshape(size, size), ((0, pad), (0, pad)))
    hi = (t >> 8).astype(jnp.int8)
    lo = ((t & 0xFF) - 128).astype(jnp.int8)
    return hi, lo


def lut_matmul_pallas(a, b, table, *, block_m: int = 128, block_n: int = 128,
                      block_k: int = 128, k_chunk: int = 8,
                      interpret: bool = False):
    """(M,K) @ (K,N) contraction with the scalar product read from ``table``.

    a: (M, K) int32; b: (K, N) int32; table: flat (2^{2n},) int32 product
    LUT (``core.lut.flat_lut``), n ≤ 8. Returns (M, N) int32. ``k_chunk`` is
    clamped to a divisor of the block. Every dim must be a multiple of its
    block size — ``ops.lut_matmul`` pads arbitrary shapes and corrects the
    f(0,0) padding artifact; direct callers get a loud error instead of
    silent garbage.
    """
    m, k = a.shape
    _, n = b.shape
    blocking.check_kernel_shapes(
        "lut_matmul_pallas", "kernels.lut_matmul.ops.lut_matmul",
        a.shape, b.shape, block_m, block_n, block_k)
    n_bits = table_width(table.shape[0])
    k_chunk = resolve_k_chunk(k_chunk, block_k)
    t_hi, t_lo = _split_table(jnp.asarray(table, jnp.int32), n_bits)
    size = t_hi.shape[0]
    grid = (m // block_m, n // block_n, k // block_k)
    # both byte planes stay resident in VMEM at every grid step
    table_spec = pl.BlockSpec((size, size), lambda i, j, kk: (0, 0))
    return pl.pallas_call(
        functools.partial(_lut_matmul_kernel, k_chunk=k_chunk,
                          n_bits=n_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            table_spec, table_spec,
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=interpret,
        name="lut_matmul",
    )(a, b, t_hi, t_lo)
