"""ProductSubstrate registry: cross-backend parity, batched conv/edge
detection against the single-image loop, and end-to-end model dispatch
(including the Pallas kernel in interpret mode)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import multiplier as mult
from repro.data import image_batch
from repro.models import registry as reg
from repro.nn import conv
from repro.nn import substrate as sub

RNG = np.random.default_rng(11)

ALL_BACKENDS = {"exact", "int8", "approx_bitexact", "approx_lut",
                "approx_stat", "approx_pallas"}


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


def test_registry_lists_all_backends():
    assert set(sub.list_substrates()) == ALL_BACKENDS


def test_spec_parsing_and_mult_reachability():
    s = sub.get_substrate("approx_lut:design_du2022")
    assert s.meta.name == "approx_lut" and s.meta.mult_name == "design_du2022"
    # explicit mult_name overrides the suffix
    s2 = sub.get_substrate("approx_lut:design_du2022", mult_name="proposed")
    assert s2.meta.mult_name == "proposed"
    # every entry in ALL_MULTIPLIERS (incl. @4/@16 variants) is reachable
    # through the bitexact backend; LUT covers the enumerable widths
    for name in mult.ALL_MULTIPLIERS:
        base, width = mult.split_width(name)
        s3 = sub.get_substrate("approx_bitexact", mult_name=name)
        assert (s3.meta.mult_name, s3.meta.width) == (base, width)
        assert s3.meta.mult_key == (name if width != 8 else base)
        if width <= 8:
            assert sub.get_substrate("approx_lut", mult_name=name).meta.width == width


def test_unknown_backend_and_wiring_raise():
    with pytest.raises(ValueError, match="unknown product substrate"):
        sub.get_substrate("systolic")
    with pytest.raises(ValueError, match="unknown multiplier wiring"):
        sub.get_substrate("approx_lut:not_a_design")
    with pytest.raises(ValueError, match="unknown multiplier wiring"):
        sub.get_substrate("approx_pallas:not_a_design")


def test_exact_backends_reject_wiring_suffix():
    """A wiring on an exact backend is a confused spec, not a no-op."""
    for spec in ("int8:design_du2022", "exact:proposed"):
        with pytest.raises(ValueError, match="takes no multiplier wiring"):
            sub.get_substrate(spec)


def test_meta_label_distinguishes_wirings():
    assert sub.get_substrate("approx_lut").meta.label == "approx_lut"
    assert sub.get_substrate("approx_lut:design_du2022").meta.label \
        == "approx_lut:design_du2022"


def test_get_substrate_is_cached():
    assert sub.get_substrate("approx_lut") is sub.get_substrate("approx_lut")


# ---------------------------------------------------------------------------
# integer-contraction parity: pallas == lut == bitexact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mkn", [
    (1, 1, 1),          # degenerate
    (5, 19, 3),         # K not a multiple of the k-chunk / pallas block
    (16, 32, 8),
    (33, 100, 17),      # every dim off the pallas block grid
    (8, 128, 4),        # K exactly one pallas block
])
def test_pallas_lut_bitexact_parity(mkn):
    """The f(0,0)=192 padding correction must make all three bit-exact
    backends agree on arbitrary (incl. non-block-multiple-K) shapes."""
    m, k, n = mkn
    a8 = RNG.integers(-128, 128, (m, k)).astype(np.int8)
    b8 = RNG.integers(-128, 128, (k, n)).astype(np.int8)
    outs = {name: np.asarray(sub.get_substrate(name).dot_int(a8, b8))
            for name in ("approx_bitexact", "approx_lut", "approx_pallas")}
    np.testing.assert_array_equal(outs["approx_bitexact"], outs["approx_lut"])
    np.testing.assert_array_equal(outs["approx_bitexact"], outs["approx_pallas"])


def test_scalar_faithful_dot_matches_scalar_sum():
    """dot_int == Σ_k scalar(a_k, b_k) for every scalar-faithful substrate."""
    a8 = RNG.integers(-128, 128, (4, 11)).astype(np.int64)
    b8 = RNG.integers(-128, 128, (11, 3)).astype(np.int64)
    for spec in sub.list_substrates():
        s = sub.get_substrate(spec)
        if not s.meta.scalar_faithful:
            continue
        oracle = np.asarray(
            s.scalar(jnp.asarray(a8[:, :, None], jnp.int32),
                     jnp.asarray(b8[None, :, :], jnp.int32))).sum(axis=1)
        got = np.asarray(s.dot_int(a8.astype(np.int8), b8.astype(np.int8)))
        np.testing.assert_array_equal(got, oracle, err_msg=spec)


# ---------------------------------------------------------------------------
# batched conv parity vs the single-image loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", sorted(ALL_BACKENDS))
def test_conv2d_batched_matches_loop_per_image(spec):
    s = sub.get_substrate(spec)
    imgs = RNG.integers(0, 128, (3, 12, 14)).astype(np.int32)
    kernel = jnp.asarray(conv.LAPLACIAN)
    got = np.asarray(conv.conv2d_batched(imgs, kernel, s))
    for i in range(imgs.shape[0]):
        ref = np.asarray(conv.conv2d_int(jnp.asarray(imgs[i]), kernel, s.scalar))
        if s.meta.scalar_faithful:
            np.testing.assert_array_equal(got[i], ref, err_msg=spec)
        else:
            # approx_stat rounds the separable correction once per output
            # element; the loop rounds per tap — difference < 1 per tap
            taps = int(np.prod(conv.LAPLACIAN.shape))
            np.testing.assert_allclose(got[i], ref, atol=taps, err_msg=spec)


def test_conv2d_batched_nhwc_channels():
    imgs = RNG.integers(0, 128, (2, 9, 9, 3)).astype(np.int32)
    s = sub.get_substrate("approx_bitexact")
    got = np.asarray(conv.conv2d_batched(imgs, conv.LAPLACIAN, s))
    assert got.shape == imgs.shape
    for b in range(2):
        for c in range(3):
            ref = np.asarray(conv.conv2d_int(
                jnp.asarray(imgs[b, :, :, c]), jnp.asarray(conv.LAPLACIAN),
                s.scalar))
            np.testing.assert_array_equal(got[b, :, :, c], ref)


# ---------------------------------------------------------------------------
# batched edge detection (acceptance: ≥8 images identical to single-image)
# ---------------------------------------------------------------------------


def test_edge_detect_batched_identical_to_single_image():
    imgs = image_batch(8, 32, 32)
    batched = np.asarray(
        conv.edge_detect_batched(imgs, "approx_bitexact:proposed"))
    assert batched.shape == imgs.shape and batched.dtype == np.uint8
    for i in range(8):
        single = np.asarray(conv.edge_detect(imgs[i], "proposed"))
        np.testing.assert_array_equal(batched[i], single)


def test_edge_detect_batched_pallas_substrate():
    imgs = image_batch(2, 16, 16)
    batched = np.asarray(conv.edge_detect_batched(imgs, "approx_pallas"))
    for i in range(2):
        single = np.asarray(conv.edge_detect(imgs[i], "proposed"))
        np.testing.assert_array_equal(batched[i], single)


def test_psnr_no_float64_warning():
    img = image_batch(2, 16, 16)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        p = conv.psnr(img[0], img[1])
    assert np.isfinite(p)


# ---------------------------------------------------------------------------
# model dispatch (serving dispatch lives in tests/test_serving.py)
# ---------------------------------------------------------------------------


def _tiny_cfg(**overrides):
    return reg.get_config("minitron-8b", n_layers=1, d_model=32, d_ff=64,
                          vocab=64, n_heads=2, n_kv_heads=2, attn_chunk=16,
                          loss_chunk=16, remat=False, **overrides)


def test_bundle_resolves_substrate_once():
    bundle = reg.build_bundle(_tiny_cfg(dot_plan="approx_lut:design_du2022"))
    assert bundle.substrate is sub.get_substrate("approx_lut:design_du2022")
    assert bundle.substrate.meta.mult_name == "design_du2022"


def test_model_smoke_approx_pallas_end_to_end():
    """approx_pallas selectable via cfg.dot_plan (interpret mode on CPU)."""
    cfg = _tiny_cfg(dot_plan="approx_pallas")
    bundle = reg.build_bundle(cfg)
    assert bundle.substrate.meta.name == "approx_pallas"
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        RNG.integers(0, cfg.vocab, (2, 16)), jnp.int32)}
    logits = bundle.prefill(params, batch)
    assert logits.shape == (2, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))

