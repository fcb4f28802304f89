"""Elementwise approximate-multiply Pallas kernel.

The simplest hardware analogue: an array of the paper's multipliers. Inputs
are int8-domain values held in int32 (TPU VPU lanes are 32-bit); tiles are
(block_m, block_n) VMEM blocks, last dim aligned to the 128-lane VPU.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

from repro.kernels.closed_form import approx_product_i32


def _kernel(a_ref, b_ref, o_ref):
    o_ref[...] = approx_product_i32(a_ref[...], b_ref[...])


def approx_mul_pallas(a, b, *, block_m: int = 256, block_n: int = 128,
                      interpret: bool = False):
    """Elementwise proposed approximate product of two int32 arrays.

    a, b: (M, N) int32 in [-128, 127]; returns (M, N) int32.
    M % block_m == 0 and N % block_n == 0 (ops.py pads).
    """
    m, n = a.shape
    grid = (m // block_m, n // block_n)
    spec = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        interpret=interpret,
        name="approx_mul",
    )(a, b)
