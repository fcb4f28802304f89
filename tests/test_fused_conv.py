"""Fused conv kernel, generated closed-form wirings, and k-slab vectorization.

The acceptance gates of the fused pipeline PR:
 * ``make_closed_form`` reproduces ``core.multiplier`` bit-exactly for every
   registered wiring (exhaustive at N=4, sampled at other widths);
 * the vectorized k-slab matmul kernels (``k_chunk > 1``) match both the
   ``k_chunk=1`` fori-equivalent body and the bit-exact substrate;
 * ``conv2d_batched(..., fused=True)`` is bit-identical to the im2col
   reference path across substrates × wirings × widths, including ragged
   H/W, NHWC, and the traced-kernel fallback.

Everything here runs in interpret mode off-TPU, so images stay small.
CI smoke selection: ``-k "fused and n4"``.
"""
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import lut as lut_lib
from repro.core import metrics
from repro.core import multiplier as mult
from repro.kernels import blocking
from repro.kernels.closed_form import (approx_product_i32, closed_form_f00,
                                       make_closed_form)
from repro.kernels.approx_matmul.kernel import resolve_k_chunk
from repro.kernels.approx_matmul.ops import closed_form_matmul
from repro.kernels.lut_matmul.ops import lut_matmul
from repro.kernels.fused_conv.ops import KERNEL_KINDS, fused_conv2d
from repro.kernels.fused_conv.ref import fused_conv_ref
from repro.nn import conv
from repro.nn import substrate as sub

RNG = np.random.default_rng(66)


def _img(h, w, lo=-128, hi=128):
    return RNG.integers(lo, hi, (h, w)).astype(np.int32)


def _pair_grid(n):
    lo, hi = -(1 << (n - 1)), 1 << (n - 1)
    v = np.arange(lo, hi, dtype=np.int32)
    return v[:, None], v[None, :]


# ---------------------------------------------------------------------------
# generated closed-form kernels vs the core model
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(mult.WIRINGS))
def test_closed_form_generator_exhaustive_n4(name):
    """Every registered wiring's generated kernel is bit-exact at N=4."""
    a, b = metrics.operand_grid(4)
    want = np.asarray(mult.make_multiplier(name, 4)(a, b))
    got = np.asarray(make_closed_form(name, 4)(a, b))
    np.testing.assert_array_equal(got, want, err_msg=name)


def test_closed_form_generator_matches_handwritten_n8():
    """The generated proposed@8 kernel equals the hand-derived closed form
    (and the core model) on the exhaustive 8-bit grid."""
    a, b = metrics.operand_grid(8)
    want = np.asarray(mult.approx_multiply(a, b))
    gen = np.asarray(make_closed_form("proposed")(a, b))
    hand = np.asarray(approx_product_i32(a, b))
    np.testing.assert_array_equal(gen, want)
    np.testing.assert_array_equal(gen, hand)


@pytest.mark.parametrize("name", ["proposed", "csp_axc1", "design_du2022",
                                  "design_strollo2020"])
@pytest.mark.parametrize("width", [3, 4, 5, 6, 7, 8])
def test_closed_form_generator_widths(name, width):
    """Sampled parity at widths 3–8, with out-of-range operands (the
    generated kernel wraps into the width's domain like the core model)."""
    fn = make_closed_form(name, width)
    ref = mult.make_multiplier(mult.WIRING_ALIASES.get(name, name), width)
    a = RNG.integers(-300, 300, (64,)).astype(np.int32)
    b = RNG.integers(-300, 300, (64,)).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(fn(a, b)), np.asarray(ref(a, b)))


@pytest.mark.parametrize("key", ["proposed", "proposed@4", "csp_axc1@5",
                                 "design_strollo2020"])
def test_closed_form_f00_matches_lut_f00(key):
    assert closed_form_f00(key) == lut_lib.f00(key)


# ---------------------------------------------------------------------------
# vectorized k-slab vs the fori-equivalent body (k_chunk=1) vs bitexact
# ---------------------------------------------------------------------------

def test_resolve_k_chunk_divides_block():
    assert resolve_k_chunk(8, 128) == 8
    assert resolve_k_chunk(8, 12) == 4   # gcd fallback keeps it valid
    assert resolve_k_chunk(5, 8) == 1
    assert resolve_k_chunk(0, 128) == 128  # gcd(0, bk): whole block at once


@pytest.mark.parametrize("name", sorted(mult.WIRINGS))
def test_kslab_closed_form_exhaustive_n4(name):
    """Vectorized (k_chunk=8) and fori-equivalent (k_chunk=1) closed-form
    matmuls agree with the bit-exact substrate on the exhaustive N=4 grid
    (K=1 forces pad correction)."""
    a, b = _pair_grid(4)
    want = np.asarray(
        sub.get_substrate(f"approx_bitexact:{name}@4").dot_int8(a, b))
    for kc in (8, 1):
        got = np.asarray(closed_form_matmul(a, b, f"{name}@4", k_chunk=kc))
        np.testing.assert_array_equal(got, want, err_msg=f"{name} kc={kc}")


def test_kslab_lut_exhaustive_n4():
    a, b = _pair_grid(4)
    flat = lut_lib.flat_lut("proposed@4")
    want = np.asarray(lut_matmul(a, b, flat, k_chunk=1))
    got = np.asarray(lut_matmul(a, b, flat, k_chunk=8))
    np.testing.assert_array_equal(got, want)


def test_kslab_ragged_k_padding():
    """k_chunk survives K that isn't a multiple of the chunk or block."""
    a = _img(9, 37)
    b = _img(37, 11)
    want = np.asarray(sub.get_substrate("approx_bitexact").dot_int(a, b))
    for kc in (1, 4, 8):
        got = np.asarray(closed_form_matmul(a, b, "proposed", k_chunk=kc))
        np.testing.assert_array_equal(got, want, err_msg=f"kc={kc}")


@pytest.mark.parametrize("mkn", [(256, 9, 1), (300, 200, 3), (129, 1, 127)])
def test_lane_dense_orientation_matches_bitexact(mkn):
    """A narrow N with a long M runs as (Bᵀ Aᵀ)ᵀ under the swapped product;
    an asymmetric wiring and a padded K (200) keep both the operand order
    and the f(0,0) correction honest there."""
    m, k, n = mkn
    a, b = _img(m, k), _img(k, n)
    key = "design_du2022@6"
    want = np.asarray(
        sub.get_substrate(f"approx_bitexact:{key}").dot_int(a, b))
    got = np.asarray(closed_form_matmul(a, b, key))
    np.testing.assert_array_equal(got, want, err_msg="closed form")
    got = np.asarray(lut_matmul(a, b, lut_lib.flat_lut(key)))
    np.testing.assert_array_equal(got, want, err_msg="lut")


# ---------------------------------------------------------------------------
# fused conv vs the im2col reference path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(mult.WIRINGS))
def test_fused_conv_wirings_n4(name):
    """CI smoke gate: fused kernel == im2col path for every wiring at N=4."""
    imgs = np.stack([_img(13, 17, lo=-8, hi=8) for _ in range(2)])
    s = sub.get_substrate(f"approx_pallas:{name}@4")
    got = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=True))
    ref = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=False))
    np.testing.assert_array_equal(got, ref, err_msg=name)


@pytest.mark.parametrize("width", [3, 4, 5, 6, 7, 8])
def test_fused_conv_widths(width):
    imgs = _img(11, 19, lo=-(1 << (width - 1)), hi=1 << (width - 1))[None]
    s = sub.get_substrate(f"approx_pallas:proposed@{width}")
    got = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=True))
    ref = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=False))
    np.testing.assert_array_equal(got, ref, err_msg=f"width={width}")


@pytest.mark.parametrize("shape", [(1, 1), (3, 3), (5, 9), (13, 17),
                                   (20, 7), (33, 65)])
def test_fused_conv_ragged_shapes(shape):
    imgs = _img(*shape)[None]
    got = np.asarray(fused_conv2d(imgs, conv.LAPLACIAN, "proposed"))
    ref = np.asarray(fused_conv_ref(imgs, conv.LAPLACIAN, "proposed"))
    np.testing.assert_array_equal(got, ref, err_msg=str(shape))


@pytest.mark.parametrize("kern", [np.ones((1, 1), np.int32),
                                  RNG.integers(-4, 5, (2, 3)).astype(np.int32),
                                  RNG.integers(-4, 5, (5, 5)).astype(np.int32)])
def test_fused_conv_kernel_shapes(kern):
    """Odd, even, and 1x1 kernel dims all contract the same taps."""
    imgs = _img(10, 14)[None]
    got = np.asarray(fused_conv2d(imgs, kern, "proposed"))
    ref = np.asarray(fused_conv_ref(imgs, kern, "proposed"))
    np.testing.assert_array_equal(got, ref, err_msg=str(kern.shape))


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_fused_conv_kernel_kinds(kind):
    """Both fused product strategies (generated closed form, flat LUT)
    produce the same bits."""
    imgs = _img(9, 12)[None]
    got = np.asarray(
        fused_conv2d(imgs, conv.LAPLACIAN, "csp_axc1@4", kernel_kind=kind))
    ref = np.asarray(fused_conv_ref(imgs, conv.LAPLACIAN, "csp_axc1@4"))
    np.testing.assert_array_equal(got, ref, err_msg=kind)


def test_fused_conv_exact_wiring_uses_lut():
    """'exact' has no CSP closed form — the fused path serves it via the
    flat LUT strategy. In-domain operands *and taps* only: the exact
    scalar model is a plain multiply and doesn't wrap out-of-range ints
    like the LUT does (conv.LAPLACIAN's center tap 8 is outside the
    signed 4-bit domain, so the 4-center discrete Laplacian is used)."""
    imgs = _img(8, 9, lo=-8, hi=8)[None]
    kern = np.array([[0, -1, 0], [-1, 4, -1], [0, -1, 0]], np.int32)
    got = np.asarray(fused_conv2d(imgs, kern, "exact@4"))
    ref = np.asarray(fused_conv_ref(imgs, kern, "exact@4"))
    np.testing.assert_array_equal(got, ref)


def test_fused_conv_nhwc():
    imgs = RNG.integers(-32, 32, (2, 9, 11, 3)).astype(np.int32)
    s = sub.get_substrate("approx_pallas:proposed@4")
    got = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=True))
    ref = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=False))
    np.testing.assert_array_equal(got, ref)


def test_fused_conv_traced_kernel_falls_back():
    """A traced kernel can't specialize the fused kernel — the auto gate
    silently takes the im2col path inside jit, still bit-identical."""
    imgs = _img(7, 9)[None]
    s = sub.get_substrate("approx_pallas:proposed@4")

    @jax.jit
    def run(k):
        return conv.conv2d_batched(imgs, k, s)

    got = np.asarray(run(jnp.asarray(conv.LAPLACIAN)))
    ref = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s, fused=False))
    np.testing.assert_array_equal(got, ref)


def test_fused_conv_edge_detect_batched_parity():
    """End to end: the batched edge pipeline through approx_pallas (which
    auto-selects the fused kernel) matches approx_bitexact."""
    imgs = RNG.integers(0, 256, (2, 16, 20)).astype(np.uint8)
    got = np.asarray(conv.edge_detect_batched(imgs, "approx_pallas:proposed@4"))
    ref = np.asarray(
        conv.edge_detect_batched(imgs, "approx_bitexact:proposed@4"))
    np.testing.assert_array_equal(got, ref)


def test_fused_true_requires_fused_capable_substrate():
    imgs = _img(6, 6)[None]
    with pytest.raises(ValueError, match="no fused conv"):
        conv.conv2d_batched(imgs, conv.LAPLACIAN, "approx_bitexact",
                            fused=True)


def test_fused_true_rejects_partitioning():
    imgs = _img(6, 6)[None]
    s = sub.get_substrate("approx_pallas:proposed@4")
    with pytest.raises(ValueError, match="incompatible with partitioning"):
        conv.conv2d_batched(imgs, conv.LAPLACIAN, s,
                            partitioning=object(), fused=True)


def test_fused_conv_rejects_bad_kernel_kind():
    imgs = _img(6, 6)[None]
    with pytest.raises(ValueError):
        fused_conv2d(imgs, conv.LAPLACIAN, "proposed", kernel_kind="mxu")


# ---------------------------------------------------------------------------
# interpret-mode resolution
# ---------------------------------------------------------------------------

def test_resolve_interpret_precedence(monkeypatch):
    monkeypatch.delenv(blocking.INTERPRET_ENV, raising=False)
    default = jax.default_backend() != "tpu"
    assert blocking.resolve_interpret() is default
    # explicit param always wins
    assert blocking.resolve_interpret(True) is True
    assert blocking.resolve_interpret(False) is False
    # env overrides the backend default, but not the explicit param
    monkeypatch.setenv(blocking.INTERPRET_ENV, "0")
    assert blocking.resolve_interpret() is False
    assert blocking.resolve_interpret(True) is True
    monkeypatch.setenv(blocking.INTERPRET_ENV, "yes")
    assert blocking.resolve_interpret() is True
    monkeypatch.setenv(blocking.INTERPRET_ENV, "bogus")
    with pytest.raises(ValueError, match=blocking.INTERPRET_ENV):
        blocking.resolve_interpret()


def test_resolve_interpret_refuses_tpu(monkeypatch):
    """On a TPU the kernels compile with Mosaic; a request for interpret
    mode there (env or argument) raises instead of running the
    interpreter."""
    monkeypatch.setattr(blocking.jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv(blocking.INTERPRET_ENV, raising=False)
    assert blocking.resolve_interpret() is False
    assert blocking.resolve_interpret(False) is False
    with pytest.raises(RuntimeError, match="interpret mode"):
        blocking.resolve_interpret(True)
    monkeypatch.setenv(blocking.INTERPRET_ENV, "1")
    with pytest.raises(RuntimeError, match="interpret mode"):
        blocking.resolve_interpret()
