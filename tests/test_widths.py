"""Width-parametric multiplier contract: N∈{4, 8, 16}.

Parity oracle is the width-N Baugh-Wooley PPM construction: the exact BW
model must reproduce a·b, and every CSP wiring's closed form must equal the
independent structural PPM/compressor model (``StructuralMultiplier``) —
exhaustively at N=4 and N=8, sampled at N=16 (the 2^32 grid is not
enumerable). Plus: LUT==bitexact per width, substrate-spec ``@N``
round-trips, quantization clamps, and width-aware error moments.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut as lut_lib
from repro.core import metrics, multiplier as m
from repro.nn import conv, quant
from repro.nn import substrate as sub

RNG = np.random.default_rng(23)

WIRING_NAMES = sorted(m.WIRINGS)


def _grid(n):
    a, b = metrics.operand_grid(n)
    return np.asarray(a), np.asarray(b)


def _sample(n, k=20000, seed=5):
    a, b = metrics.sample_operands(n, k, seed)
    return np.asarray(a), np.asarray(b)


# ---------------------------------------------------------------------------
# Baugh-Wooley reference parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [4, 8], ids=["n4", "n8"])
def test_exact_baugh_wooley_exhaustive(n):
    a, b = _grid(n)
    got = np.asarray(jax.jit(lambda x, y: m.exact_baugh_wooley(x, y, n))(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, a.astype(np.int64) * b.astype(np.int64))


def test_exact_baugh_wooley_sampled_n16():
    a, b = _sample(16)
    got = np.asarray(m.exact_baugh_wooley(jnp.asarray(a), jnp.asarray(b), 16))
    np.testing.assert_array_equal(got, a.astype(np.int64) * b.astype(np.int64))


@pytest.mark.parametrize("name", WIRING_NAMES)
@pytest.mark.parametrize("n", [4, 8], ids=["n4", "n8"])
def test_closed_form_equals_structural_exhaustive(name, n):
    """Every wiring, exhaustive over the width-N operand grid."""
    a, b = _grid(n)
    w = m.WIRINGS[name]
    closed = np.asarray(jax.jit(
        lambda x, y: m.approx_multiply_with(x, y, w, n))(
            jnp.asarray(a), jnp.asarray(b)))
    structural = np.asarray(jax.jit(m.StructuralMultiplier(n, w))(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(closed, structural)


@pytest.mark.parametrize("name", WIRING_NAMES)
def test_closed_form_equals_structural_sampled_n16(name):
    a, b = _sample(16)
    w = m.WIRINGS[name]
    closed = np.asarray(m.approx_multiply_with(
        jnp.asarray(a), jnp.asarray(b), w, 16))
    structural = np.asarray(m.StructuralMultiplier(16, w)(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(closed, structural)


def test_closed_form_equals_structural_hypothesis_n16():
    """Property-based spot check at N=16 (runs only if hypothesis exists)."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.integers(-(1 << 15), (1 << 15) - 1),
               st.integers(-(1 << 15), (1 << 15) - 1))
    @hyp.settings(max_examples=200, deadline=None)
    def check(a, b):
        closed = int(m.approx_multiply_with(
            jnp.asarray(a), jnp.asarray(b), m.PROPOSED_WIRING, 16))
        structural = int(m.StructuralMultiplier(16)(
            jnp.asarray(a), jnp.asarray(b)))
        assert closed == structural

    check()


def test_operand_wraparound_semantics():
    """Out-of-range ints wrap to their low-n-bits value in every model."""
    a = jnp.asarray([8, 200, -9])  # at n=4: 8→-8, 200→-8+... wraps
    b = jnp.asarray([3, 3, 3])
    aw = m.wrap_operand(a, 4)
    np.testing.assert_array_equal(np.asarray(aw), [-8, -8, 7])
    direct = np.asarray(m.approx_multiply_with(a, b, m.PROPOSED_WIRING, 4))
    wrapped = np.asarray(m.approx_multiply_with(aw, b, m.PROPOSED_WIRING, 4))
    np.testing.assert_array_equal(direct, wrapped)


def test_compensation_constant_tracks_expected_truncation():
    """comp_n = (n-2)·2^(n-3) = floor(E[T_T]) at every width (frac = 1/4)."""
    for n in range(4, 17):
        assert m.compensation_constant(n) == int(m.expected_truncation(n))
        assert abs(m.expected_truncation(n) - m.compensation_constant(n)) == 0.25
    assert m.compensation_constant(8) == 192  # the paper's 2^7 + 2^6
    assert m.compensation_constant(4) == 4


def test_width_bounds_rejected():
    with pytest.raises(ValueError, match="operand width"):
        m.make_multiplier("proposed", 2)
    with pytest.raises(ValueError, match="operand width"):
        m.make_multiplier("proposed", 17)
    with pytest.raises(ValueError, match="bad width suffix"):
        m.split_width("proposed@banana")


def test_wiring_aliases_resolve():
    key, fn, n = m.resolve_multiplier("csp_axc1@4")
    assert key == "design_esposito2018@4" and n == 4
    a, b = _grid(4)
    np.testing.assert_array_equal(
        np.asarray(fn(jnp.asarray(a), jnp.asarray(b))),
        np.asarray(m.ALL_MULTIPLIERS["design_esposito2018@4"](
            jnp.asarray(a), jnp.asarray(b))))


def test_all_multipliers_has_width_variants():
    for name in m.WIRINGS:
        assert f"{name}@4" in m.ALL_MULTIPLIERS
        assert f"{name}@16" in m.ALL_MULTIPLIERS
    assert set(m.default_width_names()) == {"exact", *m.WIRINGS}


# ---------------------------------------------------------------------------
# LUT == bitexact per width
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [4, 8], ids=["n4", "n8"])
def test_lut_matches_closed_form_per_width(n):
    table = lut_lib.build_lut(f"proposed@{n}")
    assert table.shape == (1 << n, 1 << n)
    a, b = _grid(n)
    direct = np.asarray(m.make_multiplier("proposed", n)(
        jnp.asarray(a), jnp.asarray(b)))
    via_lut = np.asarray(lut_lib.lut_multiply(
        jnp.asarray(a), jnp.asarray(b), jnp.asarray(table)))
    np.testing.assert_array_equal(direct, via_lut)


def test_lut_key_canonicalization_shares_tables():
    assert lut_lib.build_lut("proposed") is lut_lib.build_lut("proposed@8")
    assert lut_lib.build_lut("csp_axc5@4") is lut_lib.build_lut("design_du2022@4")


def test_lut_rejects_wide_widths():
    with pytest.raises(ValueError, match="exhaustive LUTs"):
        lut_lib.build_lut("proposed@16")


def test_error_lut_and_moments_width_aware():
    e4 = lut_lib.error_lut("proposed@4")
    assert e4.shape == (16, 16)
    mom = lut_lib.error_moments("proposed@4")
    assert abs(mom["mean"] - e4.astype(np.float64).mean()) < 1e-9
    # 4-bit errors are small absolute numbers (truncation ≤ 2^2-ish scale)
    assert mom["max_abs"] < 64


def test_substrate_lut_equals_bitexact_width4_on_arbitrary_ints():
    """Wrap semantics: parity must hold even for out-of-4-bit-range int8."""
    a8 = RNG.integers(-128, 128, (6, 13)).astype(np.int8)
    b8 = RNG.integers(-128, 128, (13, 4)).astype(np.int8)
    bx = np.asarray(sub.get_substrate("approx_bitexact:proposed@4").dot_int(a8, b8))
    lt = np.asarray(sub.get_substrate("approx_lut:proposed@4").dot_int(a8, b8))
    np.testing.assert_array_equal(bx, lt)


def test_stat_substrate_wraps_operands_at_narrow_width():
    """approx_stat's contraction must wrap out-of-range operands like its
    own scalar model (a K=1 contraction and the scalar agree exactly)."""
    s = sub.get_substrate("approx_stat:proposed@4")
    for a, b in [(8, 3), (-9, 5), (200, -1), (7, 7)]:
        got = int(s.dot_int(np.array([[a]], np.int16),
                             np.array([[b]], np.int16))[0, 0])
        want = int(s.scalar(jnp.asarray(a), jnp.asarray(b)))
        assert got == want, (a, b)


def test_substrate_dot_width16_matches_scalar_sum_mod32():
    s = sub.get_substrate("approx_bitexact:proposed@16")
    a = RNG.integers(-32768, 32768, (4, 11)).astype(np.int64)
    b = RNG.integers(-32768, 32768, (11, 3)).astype(np.int64)
    oracle = np.asarray(
        s.scalar(jnp.asarray(a[:, :, None], jnp.int32),
                 jnp.asarray(b[None, :, :], jnp.int32)),
        dtype=np.int64).sum(axis=1)
    oracle = ((oracle + 2**31) % 2**32 - 2**31).astype(np.int32)  # int32 ring
    got = np.asarray(s.dot_int(a.astype(np.int16), b.astype(np.int16)))
    np.testing.assert_array_equal(got, oracle)


# ---------------------------------------------------------------------------
# substrate spec grammar round-trip
# ---------------------------------------------------------------------------


def test_spec_roundtrip_at_width():
    for spec, backend, name, width in [
        ("approx_lut:csp_axc1@4", "approx_lut", "csp_axc1", 4),
        ("approx_bitexact:proposed@16", "approx_bitexact", "proposed", 16),
        ("approx_stat:design_du2022@4", "approx_stat", "design_du2022", 4),
    ]:
        parts = sub.parse_spec(spec)
        assert parts == (backend, name, width)
        s = sub.get_substrate(spec)
        assert (s.meta.name, s.meta.mult_name, s.meta.width) == (backend, name, width)
        assert s.meta.spec == spec
        assert sub.get_substrate(s.meta.spec) is s  # round-trip hits the cache


def test_width_unsupported_backends_reject():
    with pytest.raises(ValueError, match="approx_lut needs an enumerable"):
        sub.get_substrate("approx_lut:proposed@16")
    with pytest.raises(ValueError, match="separable error model"):
        sub.get_substrate("approx_stat:proposed@16")
    with pytest.raises(ValueError, match="enumerable product table"):
        sub.get_substrate("approx_pallas:proposed@16")
    # the LUT kernel serves narrow widths now (PR 4) — @4 must *succeed*
    assert sub.get_substrate("approx_pallas:proposed@4").meta.width == 4


def test_default_spec_width_is_8():
    assert sub.parse_spec("approx_lut") == ("approx_lut", "proposed", 8)
    assert sub.get_substrate("approx_lut").meta.width == 8
    assert sub.get_substrate("approx_lut").meta.label == "approx_lut"
    assert sub.get_substrate("approx_lut:proposed@4").meta.label \
        == "approx_lut:proposed@4"


# ---------------------------------------------------------------------------
# quantization widths
# ---------------------------------------------------------------------------


def test_quantize_bits_ranges_and_dtypes():
    x = jnp.asarray(RNG.normal(size=(64,)).astype(np.float32)) * 100.0
    q4 = quant.quantize(x, bits=4)
    assert q4.values.dtype == jnp.int8
    assert int(jnp.abs(q4.values).max()) <= 7
    q16 = quant.quantize(x, bits=16)
    assert q16.values.dtype == jnp.int16
    assert int(jnp.abs(q16.values).max()) <= 32767
    # finer width → finer reconstruction
    err4 = float(jnp.abs(q4.dequantize() - x).max())
    err16 = float(jnp.abs(q16.dequantize() - x).max())
    assert err16 < err4


# ---------------------------------------------------------------------------
# conv / edge detection across widths
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ["approx_bitexact:proposed@4",
                                  "approx_bitexact:proposed@16"])
def test_conv2d_batched_matches_loop_at_width(spec):
    s = sub.get_substrate(spec)
    n = s.meta.width
    hi = 1 << (n - 1)
    imgs = RNG.integers(0, hi, (2, 10, 11)).astype(np.int32)
    kernel = jnp.asarray(conv.LAPLACIAN)
    got = np.asarray(conv.conv2d_batched(imgs, kernel, s))
    for i in range(imgs.shape[0]):
        ref = np.asarray(conv.conv2d_int(jnp.asarray(imgs[i]), kernel, s.scalar))
        np.testing.assert_array_equal(got[i], ref, err_msg=spec)


def test_edge_detect_batched_width4_matches_single_image():
    from repro.data import image_batch

    imgs = image_batch(3, 16, 16)
    batched = np.asarray(
        conv.edge_detect_batched(imgs, "approx_bitexact:proposed@4"))
    assert batched.shape == imgs.shape and batched.dtype == np.uint8
    for i in range(3):
        single = np.asarray(conv.edge_detect(imgs[i], "proposed@4"))
        np.testing.assert_array_equal(batched[i], single)


def test_edge_detect_width16_batched_matches_single_image():
    """Width-16 edge detection is deterministic and batched==single-image.

    (No closeness-to-exact assertion: the truncated/compensated framework
    assumes both operands span the full width, while edge-detection
    coefficients are ≤ 8 — at N=16 the 2^15 truncation cut dominates the
    ~2^18 products, so absolute edge-map quality is *worse* than at N=8
    even though NMED over uniform operands improves; see
    docs/compressors.md. The parity contract is what must hold.)"""
    from repro.data import image_batch

    imgs = image_batch(2, 16, 16)
    batched = np.asarray(
        conv.edge_detect_batched(imgs, "approx_bitexact:proposed@16"))
    assert batched.shape == imgs.shape and batched.dtype == np.uint8
    for i in range(2):
        single = np.asarray(conv.edge_detect(imgs[i], "proposed@16"))
        np.testing.assert_array_equal(batched[i], single)


# ---------------------------------------------------------------------------
# sampled error metrics + energy width scaling
# ---------------------------------------------------------------------------


def test_evaluate_sampled_zero_error_for_exact():
    rep = metrics.evaluate_sampled(m.exact_multiply, "exact", 16, 4096)
    assert rep.er == 0 and rep.med == 0


def test_evaluate_rejects_unenumerable_grid():
    with pytest.raises(ValueError, match="exhaustive grid"):
        metrics.operand_grid(16)


def test_relative_error_improves_with_width():
    """Truncation error is relatively smaller at larger N (paper Eq. 5:
    E[T_T]/max|product| shrinks), so NMED must fall from 4 → 8 → 16 bit."""
    nmed = {}
    for n in (4, 8):
        nmed[n] = metrics.evaluate(
            m.make_multiplier("proposed", n), n_bits=n).nmed
    nmed[16] = metrics.evaluate_sampled(
        m.make_multiplier("proposed", 16), n_bits=16, n_samples=1 << 15).nmed
    assert nmed[16] < nmed[8] < nmed[4]


def test_energy_scales_with_width():
    from repro.core import energy

    costs = [energy.estimate("proposed", n)["area"] for n in (4, 8, 16)]
    assert costs[0] < costs[1] < costs[2]
    # default width unchanged vs the calibrated Table-5 path
    assert energy.estimate("proposed", 8) == energy.estimate("proposed")
