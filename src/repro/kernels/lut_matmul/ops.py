"""Jit'd public wrapper for the LUT-input approximate matmul kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import blocking
from repro.kernels.lut_matmul.kernel import lut_matmul_pallas, table_width


def lut_matmul(a, b, table, block_m: int = 128, block_n: int = 128,
               block_k: int = 128, k_chunk: int = 8):
    """(M,K) @ (K,N) under the approximate multiplier defined by ``table``.

    ``table`` is the flat (2^{2n},) product LUT of any wiring/width ≤ 8
    (``core.lut.flat_lut``). Pads every dim to its block multiple. Zero
    padding of the contraction dim injects f(0,0) per padded k element (the
    compensation constant fires on zero operands — faithful to the netlist),
    which is looked up from the table — it differs per wiring and width —
    and subtracted back. ``k_chunk=1`` recovers the pre-vectorization
    per-k gather walk (kept as the benchmark baseline).
    """
    return _lut_matmul_jit(a, b, table, block_m, block_n, block_k,
                           k_chunk)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k", "k_chunk"))
def _lut_matmul_jit(a, b, table, block_m, block_n, block_k, k_chunk):
    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    table = jnp.asarray(table, jnp.int32)
    n_bits = table_width(table.shape[0])
    size, off = 1 << n_bits, 1 << (n_bits - 1)
    f00 = table[(off << n_bits) | off]  # this wiring's product at (0,0)
    # the swapped product g(x, y) = f(y, x) reads the transposed table
    table_t = table.reshape(size, size).T.reshape(-1)

    def run(t):
        return lambda x, y: blocking.pad_crop_correct(
            x, y, f00,
            lambda ap, bp, bm, bn, bk: lut_matmul_pallas(
                ap, bp, t, block_m=bm, block_n=bn, block_k=bk,
                k_chunk=k_chunk, interpret=blocking.resolve_interpret()),
            block_m=block_m, block_n=block_n, block_k=block_k)

    return blocking.lane_dense(a, b, run(table), run(table_t))
