"""Jit'd public wrapper for the fused conv+multiply kernel.

``fused_conv2d(imgs, kernel, mult_key)`` runs a batched 'same' integer
convolution entirely inside one Pallas kernel — no host-side im2col patch
tensor. Two product strategies, selected by ``kernel_kind``:

* ``"closed_form"`` — the wiring's generated closed form
  (``kernels.closed_form.make_closed_form``): pure VPU integer algebra,
  partially constant-folded per static tap coefficient;
* ``"lut"`` — the wiring's product table, read at trace time: each
  distinct tap coefficient c selects one static table column, applied as
  the exact product x·c plus a compare-select per entry where the column
  deviates from it (Mosaic gathers only within one vreg, so the table
  never becomes a kernel input). The fallback for product models with
  no CSP structure, e.g. ``"exact"``, where no entry deviates.

The default ``"auto"`` picks the closed form whenever the wiring has one
and falls back to the LUT otherwise — same policy as ``PallasSubstrate``.

The kernel taps must be *concrete* integers (they specialize the kernel);
``nn.conv.conv2d_batched`` falls back to the im2col reference path when
the kernel array is traced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut as lut_lib
from repro.core import multiplier as mult
from repro.kernels import blocking
from repro.kernels.closed_form import make_closed_form
from repro.kernels.fused_conv.kernel import fused_conv_pallas

KERNEL_KINDS = ("auto", "closed_form", "lut")


def lut_tap_product(key: str):
    """Product fn ``fn(tile, c)`` reading the wiring's table column of c."""
    table = lut_lib.build_lut(key).astype(np.int64)  # (2^N, 2^N)
    n_bits = table.shape[0].bit_length() - 1
    off, mask = 1 << (n_bits - 1), (1 << n_bits) - 1
    vals = np.arange(table.shape[0]) - off  # signed operand of each row

    def fn(tile, c):
        cw = ((int(c) + off) & mask) - off  # c wrapped into the operand range
        dev = table[:, cw + off] - vals * cw
        x = mult.wrap_operand(tile, n_bits)
        out = x * cw
        for i in np.flatnonzero(dev):
            out = out + jnp.where(x == int(vals[i]), int(dev[i]), 0)
        return out

    return fn


@functools.lru_cache(maxsize=None)
def _fused_runner(key: str, kernel_kind: str, taps: tuple, block_h: int,
                  interpret: bool):
    if kernel_kind == "auto":
        try:
            make_closed_form(key)
            kernel_kind = "closed_form"
        except ValueError:  # no CSP wiring (e.g. "exact") — serve via LUT
            kernel_kind = "lut"
    if kernel_kind == "closed_form":
        product_fn = make_closed_form(key)
    elif kernel_kind == "lut":
        product_fn = lut_tap_product(key)
    else:
        raise ValueError(
            f"unknown fused-conv kernel kind {kernel_kind!r} "
            f"(known: {KERNEL_KINDS})")
    kh, kw = len(taps), len(taps[0])
    ph, pw = kh // 2, kw // 2

    @jax.jit
    def run(imgs):
        imgs = jnp.asarray(imgs, jnp.int32)
        _, h, w = imgs.shape
        bh = min(block_h, blocking.ceil_to(h, blocking.SUBLANE))
        pad_h = (-h) % bh
        hb = h + pad_h
        padded = jnp.pad(imgs, ((0, 0), (ph, ph + pad_h), (pw, pw)))
        views = tuple(
            jax.lax.slice_in_dim(padded, di, di + hb, axis=1)
            for di in range(kh))
        out = fused_conv_pallas(views, taps, product_fn, width_out=w,
                                block_h=bh, interpret=interpret)
        return out[:, :h, :]

    return run


def fused_conv2d(imgs, kernel, mult_key: str = "proposed", *,
                 kernel_kind: str = "auto", block_h: int = 64,
                 interpret: bool | None = None):
    """Batched 'same' conv of (B, H, W) int32 images, fused in one kernel.

    ``kernel`` must be a concrete (kh, kw) int array — the taps specialize
    the kernel (a traced kernel raises; use the im2col path for that).
    Coefficients outside the wiring's signed N-bit operand range wrap, per
    the multipliers' two's-complement contract — identical semantics to
    the im2col + ``dot_general`` path, which this is bit-identical to.
    """
    taps = tuple(tuple(int(c) for c in row) for row in np.asarray(kernel))
    key = mult.canonical_key(mult_key)
    run = _fused_runner(key, kernel_kind, taps, block_h,
                        blocking.resolve_interpret(interpret))
    return run(imgs)
