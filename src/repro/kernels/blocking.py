"""Shared plumbing for the Pallas kernel wrappers.

Three concerns, one home, so the kernel paths cannot silently diverge:

* pad-to-block / crop / f(0,0)-correct for the matmul kernels
  (``approx_matmul/ops.py``, ``lut_matmul/ops.py``): take an N or K dim
  that fits in one block whole (a block equal to the full dim is always
  tileable, and a 1-wide output padded to 128 lanes would cost 128× its
  HBM bytes), round a short M up to whole sublanes, zero-pad the rest up
  to block multiples, crop the result, and subtract the multiplier's
  f(0,0) per padded k element (approximate wirings map (0,0) to a
  nonzero compensation value, so k-padding injects spurious
  contributions);
* orientation (:func:`lane_dense`): a contraction with a narrow N and a
  long M (the edge path's per-pixel tap dot, ``(B·H·W, taps) @ (taps,
  1)``) runs as ``(Bᵀ Aᵀ)ᵀ`` under the swapped product, so the long dim
  lies along the 128 lanes instead of a 1-wide output padded to 128;
* interpret-mode selection (:func:`resolve_interpret`): one policy —
  explicit param beats the ``REPRO_PALLAS_INTERPRET`` env override beats
  the backend default — consumed by every ops wrapper instead of
  per-module ``_INTERPRET`` flags.
"""
from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

# TPU int32 tile: the second-to-last dim aligns to 8 sublanes, the last to
# 128 lanes — a short M rounds up to whole sublanes.
SUBLANE, LANE = 8, 128

#: env var forcing Pallas interpret mode on ("1"/"true"/...) or off.
INTERPRET_ENV = "REPRO_PALLAS_INTERPRET"

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Should a Pallas kernel run in interpret mode?

    Precedence: an explicit ``interpret`` argument wins; otherwise the
    ``REPRO_PALLAS_INTERPRET`` env var (``1/true/yes/on`` vs
    ``0/false/no/off``); otherwise interpret everywhere except on real TPU.
    Interpret mode exists only off the TPU: asking for it on a TPU raises
    instead of silently running the kernels in the interpreter. The ops
    wrappers call this at trace time, so inside a jitted wrapper the
    decision is baked into the first trace for a given shape — set the env
    var before the first kernel call, not between calls.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        env = os.environ.get(INTERPRET_ENV)
        if env is None:
            return not on_tpu
        v = env.strip().lower()
        if v not in _TRUTHY + _FALSY:
            raise ValueError(
                f"{INTERPRET_ENV}={env!r} is neither truthy {_TRUTHY} nor "
                f"falsy {_FALSY}")
        interpret = v in _TRUTHY
    if interpret and on_tpu:
        raise RuntimeError(
            "Pallas interpret mode was requested on a TPU; it exists only "
            f"for CPU runs (unset {INTERPRET_ENV} or pass interpret=False)")
    return bool(interpret)


def ceil_to(x: int, mult: int) -> int:
    """Round ``x`` up to a positive multiple of ``mult``."""
    return max(mult, ((x + mult - 1) // mult) * mult) if x > 0 else mult


def check_kernel_shapes(kernel_name: str, ops_name: str, a_shape, b_shape,
                        block_m: int, block_n: int, block_k: int) -> None:
    """Loud shape contract for the raw (block-multiple-only) kernels.

    Raises on a contraction-dim mismatch or any non-block-multiple dim —
    the raw kernels would otherwise silently compute garbage; the ops
    wrappers pad arbitrary shapes and correct the f(0,0) padding artifact.
    """
    m, k = a_shape
    k2, n = b_shape
    if k != k2:
        raise ValueError(
            f"contraction-dim mismatch: a is {tuple(a_shape)}, "
            f"b is {tuple(b_shape)}")
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"{kernel_name} requires every dim to be a multiple of its "
            f"block size: got (M, K, N)=({m}, {k}, {n}) with blocks "
            f"(block_m, block_k, block_n)=({block_m}, {block_k}, {block_n})."
            f" Call {ops_name}, which pads and corrects the f(0,0) padding "
            "artifact.")


def pad_crop_correct(a, b, f00, kernel_call: Callable, *, block_m: int,
                     block_n: int, block_k: int):
    """Run a block-multiple-only matmul kernel on arbitrary (M,K)@(K,N).

    ``kernel_call(ap, bp, bm, bn, bk)`` receives the padded operands and the
    clamped block sizes; ``f00`` is the scalar-product model's value at
    (0, 0) (python int or traced scalar) used to correct the k-padding.
    """
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm = min(block_m, ceil_to(m, SUBLANE))
    bn = n if n <= block_n else block_n
    bk = k if k <= block_k else block_k
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    ap = jnp.pad(a, ((0, pm), (0, pk)))
    bp = jnp.pad(b, ((0, pk), (0, pn)))
    out = kernel_call(ap, bp, bm, bn, bk)[:m, :n]
    if pk:
        out = out - f00 * pk
    return out


def lane_dense(a, b, run: Callable, run_swapped: Callable):
    """``run(a, b)``, or ``run_swapped(bᵀ, aᵀ)ᵀ`` when N < 128 <= M.

    ``run_swapped`` must contract under the swapped product g(x, y) =
    f(y, x) — the approximate products are not symmetric — so that
    ``Σ_k g(bᵀ[n,k], aᵀ[k,m]) = Σ_k f(a[m,k], b[k,n])`` term for term.
    """
    if b.shape[1] < LANE <= a.shape[0]:
        return run_swapped(b.T, a.T).T
    return run(a, b)
