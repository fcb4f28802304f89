"""Jit'd public wrappers for the approximate matmul kernel.

* :func:`approx_matmul` — the historical entry point: proposed@8 via the
  hand-derived closed form.
* :func:`closed_form_matmul` — any CSP wiring/width 3..8 via the generated
  closed form (``kernels.closed_form.make_closed_form``); this is what
  ``nn.substrate.PallasSubstrate`` dispatches to, so non-proposed wirings
  run pure VPU algebra instead of the LUT-gather kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import multiplier as mult
from repro.kernels import blocking
from repro.kernels.approx_matmul.kernel import approx_matmul_pallas
from repro.kernels.closed_form import (approx_product_i32, closed_form_f00,
                                       make_closed_form)
from repro.obs.trace import trace_span


@functools.lru_cache(maxsize=None)
def _f00() -> int:
    """f(0,0) of the proposed multiplier, looked up from its product table.

    Shared with ``kernels/lut_matmul`` through ``core.lut.f00`` — the value
    is per-wiring/per-width (192 only for proposed@8), so a hard-coded
    constant here would silently miscompute the moment any other wiring
    reached this kernel.
    """
    return lut_lib.f00("proposed")


def _swap(product_fn):
    return lambda x, y: product_fn(y, x)


def _contract(a, b, product_fn, f00, block_m, block_n, block_k, k_chunk):
    """The kernel on arbitrary (M,K)@(K,N): padded, cropped, f(0,0)-corrected
    and lane-dense oriented (``blocking.lane_dense``)."""
    def run(fn):
        return lambda x, y: blocking.pad_crop_correct(
            x, y, f00,
            lambda ap, bp, bm, bn, bk: approx_matmul_pallas(
                ap, bp, product_fn=fn, block_m=bm, block_n=bn,
                block_k=bk, k_chunk=k_chunk,
                interpret=blocking.resolve_interpret()),
            block_m=block_m, block_n=block_n, block_k=block_k)

    a = jnp.asarray(a, jnp.int32)
    b = jnp.asarray(b, jnp.int32)
    return blocking.lane_dense(a, b, run(product_fn), run(_swap(product_fn)))


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k", "k_chunk"))
def _approx_matmul_jit(a, b, block_m, block_n, block_k, k_chunk):
    return _contract(a, b, approx_product_i32, _f00(), block_m, block_n,
                     block_k, k_chunk)


def approx_matmul(a, b, block_m: int = 128, block_n: int = 128,
                  block_k: int = 128, k_chunk: int = 8):
    """(M,K) @ (K,N) under the proposed approximate multiplier.

    Pads every dim to its block multiple (a dim that fits in one block is
    taken whole; see ``blocking``). Zero-padding the contraction dim
    injects f(0,0)=192 per padded k element (the compensation constant fires
    on zero operands — faithful to the netlist), which is subtracted back.
    ``k_chunk=1`` recovers the pre-vectorization scalar k-walk (kept as the
    benchmark baseline).
    """
    (m, k), (_, n) = jnp.shape(a), jnp.shape(b)
    with trace_span("kernel.approx_matmul", "kernel", m=m, k=k, n=n):
        return _approx_matmul_jit(a, b, block_m, block_n, block_k, k_chunk)


@functools.lru_cache(maxsize=None)
def _closed_form_runner(key: str, block_m: int, block_n: int, block_k: int,
                        k_chunk: int):
    product_fn = make_closed_form(key)
    f00 = closed_form_f00(key)

    @jax.jit
    def run(a, b):
        return _contract(a, b, product_fn, f00, block_m, block_n, block_k,
                         k_chunk)

    return run


def closed_form_matmul(a, b, mult_key: str = "proposed", *,
                       block_m: int = 128, block_n: int = 128,
                       block_k: int = 128, k_chunk: int = 8):
    """(M,K) @ (K,N) under any CSP wiring's *generated* closed form.

    ``mult_key``: ``"name[@N]"`` (aliases resolve). Same pad/crop/f(0,0)
    contract as :func:`approx_matmul`; the jitted runner is cached per
    (wiring, block sizes, k_chunk).
    """
    key = mult.canonical_key(mult_key)
    run = _closed_form_runner(key, block_m, block_n, block_k, k_chunk)
    (m, k), (_, n) = jnp.shape(a), jnp.shape(b)
    with trace_span("kernel.closed_form_matmul", "kernel", mult=key,
                    m=m, k=k, n=n):
        return run(a, b)
