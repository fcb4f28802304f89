"""LUT-input Pallas matmul kernel + width-parametric ``approx_pallas``.

Parity contract: the flat-table gather kernel (interpret mode on CPU) must
be bit-identical to ``approx_bitexact`` for every wiring in
``core.multiplier.WIRINGS`` — exhaustively over the N=4 operand grid (the
CI smoke gate, ``-k "exhaustive and n4"``), on ragged shapes that force
m/n/k padding, and end-to-end through the substrate registry and the
edge-detection service. Plus the satellite regressions: per-wiring f(0,0)
k-padding correction (the hard-coded 192 miscomputed any other wiring),
loud divisibility errors on the raw kernels, and strict spec parsing.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut as lut_lib
from repro.core import multiplier as mult
from repro.kernels.approx_matmul.kernel import approx_matmul_pallas
from repro.kernels.approx_matmul.ops import approx_matmul
from repro.kernels.lut_matmul.kernel import lut_matmul_pallas, table_width
from repro.kernels.lut_matmul.ops import lut_matmul
from repro.kernels.lut_matmul.ref import lut_matmul_ref
from repro.nn import substrate as sub

RNG = np.random.default_rng(41)

WIRING_NAMES = sorted(mult.WIRINGS)


def _pair_grid(n):
    """All width-n operand pairs as a (2^n, 1) @ (1, 2^n) K=1 matmul.

    K=1 also forces k-padding to the kernel's minimum block, so every
    exhaustive run exercises the f(0,0) correction too.
    """
    lo, hi = -(1 << (n - 1)), 1 << (n - 1)
    v = np.arange(lo, hi, dtype=np.int32)
    return v[:, None], v[None, :]


# ---------------------------------------------------------------------------
# kernel-level parity (CI smoke gate: -k "exhaustive and n4")
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", WIRING_NAMES)
def test_lut_kernel_exhaustive_n4(name):
    """Every wiring, all 256 width-4 operand pairs through the kernel."""
    a, b = _pair_grid(4)
    flat = lut_lib.flat_lut(f"{name}@4")
    got = np.asarray(lut_matmul(a, b, flat))
    want = np.asarray(mult.make_multiplier(name, 4)(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want, err_msg=name)


def test_lut_kernel_exhaustive_n4_out_of_range_wraps():
    """Gather indices mask to N bits: out-of-range ints hit the same
    entries the closed form's operand wraparound computes."""
    flat = lut_lib.flat_lut("proposed@4")
    a = np.array([[8, 200, -9, 7]], np.int32).T   # wrap to -8, -8, 7, 7
    b = np.array([[3, -128, 127, 0]], np.int32)
    got = np.asarray(lut_matmul(a, b, flat))
    want = np.asarray(mult.make_multiplier("proposed", 4)(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("mkn", [
    (1, 1, 1),          # degenerate
    (17, 33, 9),        # every dim off the block grid (matches approx_matmul suite)
    (5, 19, 3),
    (8, 128, 4),        # K exactly one block
])
@pytest.mark.parametrize("key", ["proposed", "design_strollo2020@4"])
def test_lut_kernel_ragged_shapes(mkn, key):
    m, k, n = mkn
    a = RNG.integers(-128, 128, (m, k)).astype(np.int32)
    b = RNG.integers(-128, 128, (k, n)).astype(np.int32)
    flat = lut_lib.flat_lut(key)
    got = np.asarray(lut_matmul(a, b, flat))
    ref = np.asarray(lut_matmul_ref(a, b, flat))
    np.testing.assert_array_equal(got, ref, err_msg=f"{key} {mkn}")


def test_lut_kernel_block_sizes():
    a = RNG.integers(-128, 128, (96, 96)).astype(np.int32)
    b = RNG.integers(-128, 128, (96, 96)).astype(np.int32)
    flat = lut_lib.flat_lut("proposed")
    ref = np.asarray(lut_matmul_ref(a, b, flat))
    for bm, bn, bk in [(32, 32, 32), (96, 96, 96), (48, 128, 8)]:
        got = np.asarray(lut_matmul(a, b, flat,
                                    block_m=bm, block_n=bn, block_k=bk))
        np.testing.assert_array_equal(got, ref, err_msg=f"{bm},{bn},{bk}")


def test_flat_lut_layout_matches_square_table():
    """flat[(a+off)<<n | (b+off)] must equal table[a+off, b+off]."""
    for key in ("proposed@4", "design_strollo2020"):
        table = lut_lib.build_lut(key)
        flat = lut_lib.flat_lut(key)
        n = table_width(flat.shape[0])
        assert table.shape == (1 << n, 1 << n)
        np.testing.assert_array_equal(flat.reshape(table.shape), table)


# ---------------------------------------------------------------------------
# per-wiring f(0,0) k-padding correction (regression: hard-coded 192)
# ---------------------------------------------------------------------------


def test_f00_shared_lookup_values():
    assert lut_lib.f00("proposed") == 192          # the paper's constant
    assert lut_lib.f00("proposed@4") == 4
    assert lut_lib.f00("design_strollo2020") == 64  # ≠ 192: the latent bug
    assert lut_lib.f00("design_strollo2020@4") == -4
    assert lut_lib.f00("exact") == 0


def test_kpad_correction_is_per_wiring_regression():
    """Contraction with k % block_k != 0 through a wiring whose f(0,0)
    differs from the proposed 192 — a hard-coded correction miscomputes
    every output element by (f00_wiring - 192) · pad."""
    key = "design_strollo2020"
    assert lut_lib.f00(key) != lut_lib.f00("proposed")
    m, k, n = 4, 3, 2                    # k=3 pads to the min block of 8
    a = RNG.integers(-128, 128, (m, k)).astype(np.int8)
    b = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    got = np.asarray(sub.get_substrate(f"approx_pallas:{key}").dot_int(a, b))
    want = np.asarray(
        sub.get_substrate(f"approx_bitexact:{key}").dot_int(a, b))
    np.testing.assert_array_equal(got, want)


def test_approx_matmul_kpad_correction_still_proposed():
    """The closed-form wrapper's correction now reads from the shared
    table lookup; proposed parity on k-padded shapes must be unchanged."""
    a = RNG.integers(-128, 128, (4, 3)).astype(np.int32)
    b = RNG.integers(-128, 128, (3, 2)).astype(np.int32)
    got = np.asarray(approx_matmul(a, b))
    want = np.asarray(mult.approx_multiply(
        a[:, :, None], b[None, :, :])).sum(axis=1)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# loud divisibility errors on the raw kernels
# ---------------------------------------------------------------------------


def test_approx_matmul_pallas_rejects_non_block_multiple():
    a = np.zeros((100, 128), np.int32)
    b = np.zeros((128, 128), np.int32)
    with pytest.raises(ValueError, match="multiple of .* block size"):
        approx_matmul_pallas(a, b, interpret=True)


def test_lut_matmul_pallas_rejects_non_block_multiple():
    flat = jnp.asarray(lut_lib.flat_lut("proposed"))
    a = np.zeros((128, 100), np.int32)
    b = np.zeros((100, 128), np.int32)
    with pytest.raises(ValueError, match="multiple of .* block size"):
        lut_matmul_pallas(a, b, flat, interpret=True)


def test_pallas_kernels_reject_shape_mismatch():
    flat = jnp.asarray(lut_lib.flat_lut("proposed"))
    a = np.zeros((128, 128), np.int32)
    b = np.zeros((64, 128), np.int32)
    with pytest.raises(ValueError, match="contraction-dim mismatch"):
        approx_matmul_pallas(a, b, interpret=True)
    with pytest.raises(ValueError, match="contraction-dim mismatch"):
        lut_matmul_pallas(a, b, flat, interpret=True)


def test_lut_matmul_rejects_non_lut_table():
    with pytest.raises(ValueError, match="flat product-LUT"):
        table_width(100)


# ---------------------------------------------------------------------------
# substrate-level: approx_pallas ≡ approx_bitexact at every wiring/width
# ---------------------------------------------------------------------------


def test_pallas_substrate_every_wiring_width_constructs():
    for name in WIRING_NAMES:
        for n in range(mult.MIN_BITS, lut_lib.MAX_LUT_BITS + 1):
            s = sub.get_substrate(f"approx_pallas:{name}@{n}")
            assert s.meta.name == "approx_pallas"
            assert (s.meta.mult_name, s.meta.width) == (name, n)
            assert s.meta.bit_exact and s.meta.scalar_faithful


def test_pallas_substrate_fast_path_vs_lut_path_metadata():
    # every CSP wiring/width gets the generated closed-form kernel ("vpu")
    assert sub.get_substrate("approx_pallas").meta.cost_hint == "vpu"
    assert sub.get_substrate(
        "approx_pallas:proposed@4").meta.cost_hint == "vpu"
    assert sub.get_substrate(
        "approx_pallas:design_du2022").meta.cost_hint == "vpu"
    # the LUT kernel remains as the non-CSP fallback and an explicit opt-in
    assert sub.get_substrate("approx_pallas:exact").meta.cost_hint == "mxu"
    forced = sub.PallasSubstrate("design_du2022", kernel="lut")
    assert forced.meta.cost_hint == "mxu"
    with pytest.raises(ValueError, match="unknown multiplier wiring"):
        sub.PallasSubstrate("exact", kernel="closed_form")


def test_pallas_substrate_rejects_unenumerable_width():
    with pytest.raises(ValueError, match="enumerable product table"):
        sub.get_substrate("approx_pallas:proposed@16")


@pytest.mark.parametrize("name", WIRING_NAMES)
def test_pallas_substrate_exhaustive_n4_matches_bitexact(name):
    """Acceptance: bit-identical to approx_bitexact on the exhaustive N=4
    grid (as a K=1 contraction, so the pad correction fires too)."""
    a, b = _pair_grid(4)
    got = np.asarray(
        sub.get_substrate(f"approx_pallas:{name}@4").dot_int(a, b))
    want = np.asarray(
        sub.get_substrate(f"approx_bitexact:{name}@4").dot_int(a, b))
    np.testing.assert_array_equal(got, want, err_msg=name)


@pytest.mark.parametrize("spec_suffix", ["design_du2022", "csp_axc1@4",
                                         "proposed@5"])
def test_pallas_substrate_sampled_matches_bitexact(spec_suffix):
    """Sampled parity incl. shapes that force k-padding, at N=8 and odd
    widths, through alias resolution."""
    ps = sub.get_substrate(f"approx_pallas:{spec_suffix}")
    bx = sub.get_substrate(f"approx_bitexact:{spec_suffix}")
    for m, k, n in [(5, 19, 3), (17, 33, 9)]:
        a = RNG.integers(-128, 128, (m, k)).astype(np.int8)
        b = RNG.integers(-128, 128, (k, n)).astype(np.int8)
        np.testing.assert_array_equal(
            np.asarray(ps.dot_int(a, b)), np.asarray(bx.dot_int(a, b)),
            err_msg=f"{spec_suffix} {(m, k, n)}")


def test_pallas_substrate_scalar_faithful_lut_path():
    """dot_int == Σ_k scalar(a_k, b_k) on the LUT path too."""
    s = sub.get_substrate("approx_pallas:design_strollo2020@4")
    a = RNG.integers(-8, 8, (4, 11)).astype(np.int8)
    b = RNG.integers(-8, 8, (11, 3)).astype(np.int8)
    oracle = np.asarray(s.scalar(jnp.asarray(a[:, :, None], jnp.int32),
                                 jnp.asarray(b[None, :, :], jnp.int32))
                        ).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(s.dot_int(a, b)), oracle)


# ---------------------------------------------------------------------------
# strict spec parsing (bugfix: malformed specs used to parse as well-formed)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    "exact:",                      # empty wiring suffix
    "approx_pallas:proposed@8 ",   # trailing whitespace
    " approx_lut",                 # leading whitespace
    "approx_lut :proposed",        # inner whitespace
    ":proposed",                   # empty backend
    "",                            # empty spec
    "approx_lut:@4",               # width without a wiring name
])
def test_malformed_specs_rejected(bad):
    with pytest.raises(ValueError, match="mult_name"):
        sub.parse_spec(bad)
    with pytest.raises(ValueError, match="mult_name"):
        sub.get_substrate(bad)


def test_empty_wiring_before_width_rejected_via_mult_name_arg():
    """'@4' alone must not silently fall back to the proposed wiring."""
    with pytest.raises(ValueError, match="mult_name"):
        sub.get_substrate("approx_bitexact", mult_name="@4")


def test_core_layer_rejects_malformed_width_and_empty_wiring():
    """The strictness holds at the core.multiplier layer too, not just the
    spec-string parser: int()'s whitespace/sign tolerance must not turn a
    typo into a well-formed key, and a bare '@N' must not silently default
    to the proposed wiring."""
    for bad in ("proposed@ 8", "proposed@+8", "proposed@-8", "proposed@",
                "proposed@８"):  # full-width '8': unicode digit, not ASCII
        with pytest.raises(ValueError, match="bad width suffix"):
            mult.split_width(bad)
        with pytest.raises(ValueError):  # whitespace or width-suffix layer
            sub.get_substrate(f"approx_lut:{bad}")
    with pytest.raises(ValueError, match="wiring name"):
        mult.resolve_multiplier("@4")


def test_well_formed_specs_still_parse():
    assert sub.parse_spec("approx_pallas:csp_axc1@4") == \
        ("approx_pallas", "csp_axc1", 4)
    assert sub.parse_spec("exact") == ("exact", "proposed", 8)
    s = sub.get_substrate("approx_pallas:csp_axc1@4")
    assert s.meta.spec == "approx_pallas:csp_axc1@4"
    assert sub.get_substrate(s.meta.spec) is s
