"""The ``dot_general`` contraction surface: dimension numbers, QuantPolicy,
Partitioning.

* dimension-number handling (batch dims, transposed contractions, multi free
  dims) against ``jax.lax.dot_general`` on the exact backend, and against
  stacked 2-D calls on the approx backends;
* the float path (QuantPolicy) is bit-identical to the historical ``dot``
  wrapper, supports per-tensor/per-channel modes and pinned scales;
* the epsilon-guarded scale: all-zero activations produce exact zeros (the
  zero-image → zero-edge-map regression), never NaN;
* sharded-vs-unsharded bit-identity under 8 forced host devices, via the
  ``tests/test_distributed.py`` subprocess harness (per-K-shard f(0,0)
  correction, psum_scatter vs psum fallback, non-divisible M and K).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_distributed import run_py

from repro.launch.mesh import make_mesh
from repro.nn import conv
from repro.nn import substrate as sub
from repro.nn.substrate import ContractionSpec, Partitioning, QuantPolicy

RNG = np.random.default_rng(7)

ALL_SPECS = ("exact", "int8", "approx_bitexact", "approx_lut",
             "approx_stat", "approx_pallas")


# ---------------------------------------------------------------------------
# dimension-number handling (integer domain) vs jax.lax.dot_general
# ---------------------------------------------------------------------------

# (lhs_shape, rhs_shape, dimension_numbers)
DIM_CASES = [
    # plain matmul
    ((5, 7), (7, 3), (((1,), (0,)), ((), ()))),
    # negative-axis default (the MATMUL_DIMS convention)
    ((5, 7), (7, 3), (((-1,), (0,)), ((), ()))),
    # transposed lhs contraction: x is (K, M)
    ((7, 5), (7, 3), (((0,), (0,)), ((), ()))),
    # transposed rhs: w is (N, K)
    ((5, 7), (3, 7), (((1,), (1,)), ((), ()))),
    # batch dims
    ((2, 5, 7), (2, 7, 3), (((2,), (1,)), ((0,), (0,)))),
    # batch dim not leading on the rhs
    ((2, 5, 7), (7, 2, 3), (((2,), (0,)), ((0,), (1,)))),
    # multiple lhs free dims (the im2col conv shape)
    ((2, 3, 4, 9), (9, 1), (((3,), (0,)), ((), ()))),
    # multiple contracting dims
    ((5, 2, 3), (2, 3, 4), (((1, 2), (0, 1)), ((), ()))),
    # rank-1 lhs (historical dot on a vector)
    ((7,), (7, 3), (((0,), (0,)), ((), ()))),
]


@pytest.mark.parametrize("case", DIM_CASES,
                         ids=[str(i) for i in range(len(DIM_CASES))])
def test_exact_dims_match_lax_dot_general(case):
    lhs_shape, rhs_shape, dims = case
    a = RNG.integers(-100, 100, lhs_shape).astype(np.int8)
    b = RNG.integers(-100, 100, rhs_shape).astype(np.int8)
    got = np.asarray(sub.get_substrate("exact").dot_general(
        jnp.asarray(a), jnp.asarray(b), ContractionSpec(dims)))
    norm = tuple(tuple(tuple(d % len(s) for d in axes)
                       for axes, s in zip(pair, (lhs_shape, rhs_shape)))
                 for pair in dims)
    ref = np.asarray(jax.lax.dot_general(
        jnp.asarray(a, jnp.int32), jnp.asarray(b, jnp.int32), norm))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("spec", ["approx_bitexact", "approx_lut"])
def test_batch_dims_match_stacked_2d(spec):
    """Batched contraction == per-slice dot_int, and lut == bitexact."""
    s = sub.get_substrate(spec)
    a = RNG.integers(-128, 128, (3, 5, 19)).astype(np.int8)
    b = RNG.integers(-128, 128, (3, 19, 4)).astype(np.int8)
    dims = (((2,), (1,)), ((0,), (0,)))
    got = np.asarray(s.dot_general(jnp.asarray(a), jnp.asarray(b),
                                   ContractionSpec(dims)))
    ref = np.stack([np.asarray(s.dot_int(a[i], b[i])) for i in range(3)])
    np.testing.assert_array_equal(got, ref, err_msg=spec)


def test_conv2d_batched_still_matches_loop():
    """The im2col + dot_general rewrite keeps the tap-loop parity."""
    imgs = RNG.integers(0, 128, (2, 10, 11)).astype(np.int32)
    kernel = jnp.asarray(conv.LAPLACIAN)
    s = sub.get_substrate("approx_bitexact")
    got = np.asarray(conv.conv2d_batched(imgs, kernel, s))
    for i in range(imgs.shape[0]):
        ref = np.asarray(conv.conv2d_int(jnp.asarray(imgs[i]), kernel,
                                         s.scalar))
        np.testing.assert_array_equal(got[i], ref)


def test_dimension_number_validation():
    s = sub.get_substrate("exact")
    a = jnp.zeros((4, 5), jnp.int8)
    b = jnp.zeros((6, 3), jnp.int8)
    with pytest.raises(ValueError, match="contracting dimension mismatch"):
        s.dot_general(a, b, ContractionSpec((((1,), (0,)), ((), ()))))
    with pytest.raises(ValueError, match="out of range"):
        s.dot_general(a, a, ContractionSpec((((3,), (0,)), ((), ()))))
    with pytest.raises(ValueError, match="duplicate"):
        s.dot_general(a, a, ContractionSpec((((1, 1), (0, 0)), ((), ()))))
    with pytest.raises(ValueError, match="both contracting and batch"):
        s.dot_general(a, a, ContractionSpec((((0,), (0,)), ((0,), (1,)))))
    with pytest.raises(ValueError, match="must pair up"):
        s.dot_general(a, a, ContractionSpec((((1,), ()), ((), ()))))
    with pytest.raises(TypeError, match="integer-domain"):
        sub.get_substrate("int8").dot_general(
            jnp.zeros((4, 5), jnp.float32), jnp.zeros((5, 3), jnp.float32))


# ---------------------------------------------------------------------------
# QuantPolicy: float path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ALL_SPECS)
def test_float_path_bit_identical_to_dot_wrapper(spec):
    s = sub.get_substrate(spec)
    x = jnp.asarray(RNG.normal(size=(3, 5, 24)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(24, 6)).astype(np.float32))
    ref = np.asarray(s.dot(x, w))
    got = np.asarray(s.dot_general(
        x, w, ContractionSpec.matmul(quant=QuantPolicy())))
    np.testing.assert_array_equal(got, ref, err_msg=spec)


def test_quant_modes_and_bits():
    x = jnp.asarray(RNG.normal(size=(4, 16)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(16, 8)).astype(np.float32))
    ref = jnp.dot(x, w)
    s = sub.get_substrate("approx_bitexact")
    for policy in (QuantPolicy(), QuantPolicy(w_mode="per_tensor"),
                   QuantPolicy(x_mode="per_channel")):
        out = s.dot_general(x, w, ContractionSpec.matmul(quant=policy))
        assert out.shape == ref.shape
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        assert rel < 0.2, (policy, rel)
    # narrower-than-substrate codes: int4 on the exact int backend (on an
    # approx multiplier the ~constant absolute truncation error would swamp
    # the tiny int4 products — that pairing is legal but useless)
    out4 = sub.get_substrate("int8").dot_general(
        x, w, ContractionSpec.matmul(quant=QuantPolicy(bits=4)))
    rel = float(jnp.linalg.norm(out4 - ref) / jnp.linalg.norm(ref))
    assert 0 < rel < 0.5, rel


def test_pinned_scales_reproduce_dynamic():
    """Pinning the dynamically-derived scales gives the identical result —
    the scale-reuse contract the policy extraction exists for."""
    s = sub.get_substrate("approx_lut")
    x = jnp.asarray(RNG.normal(size=(4, 16)).astype(np.float32))
    w = jnp.asarray(RNG.normal(size=(16, 8)).astype(np.float32))
    qm = 127.0
    x_scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / qm
    w_scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8) / qm  # (N,)
    dyn = np.asarray(s.dot(x, w))
    pinned = np.asarray(s.dot_general(x, w, ContractionSpec.matmul(
        quant=QuantPolicy(x_scale=x_scale, w_scale=w_scale))))
    np.testing.assert_array_equal(pinned, dyn)
    # a pinned scale really is pinned: reusing it on a rescaled activation
    # tensor changes the output by exactly that rescaling of the codes
    half = np.asarray(s.dot_general(0.5 * x, w, ContractionSpec.matmul(
        quant=QuantPolicy(x_scale=x_scale, w_scale=w_scale))))
    assert not np.array_equal(half, dyn)


def test_quant_policy_validation():
    with pytest.raises(ValueError, match="x_mode"):
        QuantPolicy(x_mode="per_row")
    with pytest.raises(ValueError, match="bits"):
        QuantPolicy(bits=1)
    with pytest.raises(ValueError, match="eps"):
        QuantPolicy(eps=0.0)
    s = sub.get_substrate("approx_lut:proposed@4")
    x = jnp.ones((2, 8), jnp.float32)
    w = jnp.ones((8, 2), jnp.float32)
    with pytest.raises(ValueError, match="exceeds the substrate operand"):
        s.dot_general(x, w, ContractionSpec.matmul(quant=QuantPolicy(bits=8)))


# ---------------------------------------------------------------------------
# epsilon-guarded scale: zero activations / zero image regression
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", ALL_SPECS)
def test_zero_activations_give_zero_output(spec):
    """An all-zero activation tensor must produce finite (near-)zero output:
    the epsilon guard keeps the per-tensor scale from degenerating to 0/0.
    The approx backends' compensation constant (f(0,b) = +192 at N=8, true
    to the netlist) contributes only through the tiny guarded scale, so it
    vanishes below float precision instead of poisoning the output."""
    s = sub.get_substrate(spec)
    x = jnp.zeros((4, 16), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(16, 8)).astype(np.float32))
    out = np.asarray(s.dot(x, w))
    assert np.isfinite(out).all(), spec
    assert (np.abs(out) < 1e-6).all(), (spec, np.abs(out).max())
    if s.meta.name in ("exact", "int8"):
        assert (out == 0).all(), spec


@pytest.mark.parametrize("spec", ALL_SPECS)
def test_zero_image_gives_zero_edge_map(spec):
    """Zero image → zero edge map through the quantized float path.

    (The integer netlist path intentionally differs: a zero pixel still
    fires the compensation constant — f(0,b)=+192 at N=8 — so the bit-true
    integer edge map of a black image is the constant response, preserved
    by the parity suite. The float path's epsilon-guarded per-tensor scale
    is what turns that constant bias into an exact-zero uint8 map.)"""
    s = sub.get_substrate(spec)
    imgs = jnp.zeros((2, 12, 12), jnp.float32)     # zero image, float domain
    patches = conv._im2col(imgs, 3, 3)             # (B, H, W, 9)
    kernel = jnp.asarray(conv.LAPLACIAN, jnp.float32).reshape(9, 1)
    out = np.asarray(s.dot_general(
        patches, kernel,
        ContractionSpec((((3,), (0,)), ((), ())), quant=QuantPolicy())))
    assert np.isfinite(out).all(), spec
    edge_map = np.clip(np.round(out[..., 0]), 0, 255).astype(np.uint8)
    assert (edge_map == 0).all(), (spec, np.abs(out).max())


# ---------------------------------------------------------------------------
# Partitioning: in-process (1-device mesh) behaviour + validation
# ---------------------------------------------------------------------------


def _mesh1():
    return make_mesh((1,), ("data",))


def test_partitioning_validation():
    mesh = _mesh1()
    with pytest.raises(ValueError, match="at least one"):
        Partitioning(mesh, m_axis=None, k_axis=None)
    with pytest.raises(ValueError, match="not a mesh axis"):
        Partitioning(mesh, m_axis="model")
    mesh2 = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="must differ"):
        Partitioning(mesh2, m_axis="data", k_axis="data")


def test_partitioned_single_device_bit_identical():
    """The shard_map lowering itself (1-device mesh) changes nothing."""
    part = Partitioning(_mesh1(), m_axis="data")
    a = RNG.integers(-128, 128, (5, 19)).astype(np.int8)
    b = RNG.integers(-128, 128, (19, 3)).astype(np.int8)
    for spec in ("approx_bitexact", "approx_lut", "int8"):
        s = sub.get_substrate(spec)
        ref = np.asarray(s.dot_int(a, b))
        got = np.asarray(s.dot_general(
            jnp.asarray(a), jnp.asarray(b),
            ContractionSpec(partitioning=part)))
        np.testing.assert_array_equal(got, ref, err_msg=spec)


def test_partitioned_batch_dims_not_supported():
    part = Partitioning(_mesh1(), m_axis="data")
    a = jnp.zeros((2, 4, 8), jnp.int8)
    b = jnp.zeros((2, 8, 3), jnp.int8)
    with pytest.raises(NotImplementedError, match="batch dimensions"):
        sub.get_substrate("approx_bitexact").dot_general(
            a, b, ContractionSpec((((2,), (1,)), ((0,), (0,))),
                                  partitioning=part))


def test_partitioning_scope_is_ambient():
    assert sub.current_partitioning() is None
    p = Partitioning(_mesh1(), m_axis="data")
    with sub.partitioning_scope(p):
        assert sub.current_partitioning() is p
        with sub.partitioning_scope(None):
            assert sub.current_partitioning() is None
        assert sub.current_partitioning() is p
    assert sub.current_partitioning() is None


# ---------------------------------------------------------------------------
# sharded parity on 8 forced host devices (subprocess harness)
# ---------------------------------------------------------------------------


def test_sharded_bit_identity_8_devices():
    """shard_map dot_general == unsharded dot_int bit-exactly on a (2, 4)
    mesh: data-parallel M, reduce-scattered K, per-K-shard f(0,0)
    correction. Covers non-divisible M and K (zero-pad + global f(0,0)
    fix-up — design_strollo2020 has a different f(0,0) than proposed, so a
    wrong-constant bug cannot cancel) and the psum fallback when N doesn't
    divide the k axis."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.nn import substrate as sub
        from repro.nn.substrate import ContractionSpec, Partitioning

        rng = np.random.default_rng(3)
        mesh = make_mesh((2, 4), ("data", "model"))
        parts = [
            Partitioning(mesh, m_axis="data"),                  # M only
            Partitioning(mesh, m_axis=None, k_axis="model"),    # K only
            Partitioning(mesh, m_axis="data", k_axis="model"),  # M + K
        ]
        shapes = [
            (8, 32, 8),    # everything divides; psum_scatter path
            (5, 19, 3),    # M, K, N all non-divisible; psum fallback
            (16, 64, 4),   # N == k_shards; psum_scatter path
        ]
        specs = ("exact", "int8", "approx_bitexact",
                 "approx_bitexact:design_strollo2020", "approx_lut",
                 "approx_lut:csp_axc1@4")
        for spec in specs:
            s = sub.get_substrate(spec)
            for m, k, n in shapes:
                a = rng.integers(-128, 128, (m, k)).astype(np.int8)
                b = rng.integers(-128, 128, (k, n)).astype(np.int8)
                ref = np.asarray(s.dot_int(a, b))
                for part in parts:
                    got = np.asarray(s.dot_general(
                        jnp.asarray(a), jnp.asarray(b),
                        ContractionSpec(partitioning=part)))
                    np.testing.assert_array_equal(
                        got, ref,
                        err_msg=f"{spec} {(m, k, n)} m={part.m_axis} "
                                f"k={part.k_axis}")
        print("sharded parity ok", len(specs) * len(shapes) * len(parts))
    """)
    assert "sharded parity ok 54" in out


def test_sharded_quantized_float_path_8_devices():
    """The full QuantPolicy float path under a Partitioning equals the
    unsharded float dot bit-exactly for the integer-exact backends (int32
    partial sums reduce exactly; the scales are computed unsharded)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.nn import substrate as sub
        from repro.nn.substrate import ContractionSpec, Partitioning, \\
            QuantPolicy

        rng = np.random.default_rng(5)
        mesh = make_mesh((2, 4), ("data", "model"))
        part = Partitioning(mesh, m_axis="data", k_axis="model")
        x = jnp.asarray(rng.normal(size=(6, 40)).astype(np.float32))
        w = jnp.asarray(rng.normal(size=(40, 8)).astype(np.float32))
        for spec in ("int8", "approx_bitexact", "approx_lut"):
            s = sub.get_substrate(spec)
            ref = np.asarray(s.dot(x, w))
            got = np.asarray(s.dot_general(x, w, ContractionSpec.matmul(
                quant=QuantPolicy(), partitioning=part)))
            np.testing.assert_array_equal(got, ref, err_msg=spec)
        # exact float: psum reduction order => allclose, not bit-identity
        e = sub.get_substrate("exact")
        got = np.asarray(e.dot_general(x, w, ContractionSpec.matmul(
            quant=QuantPolicy(), partitioning=part)))
        np.testing.assert_allclose(got, np.asarray(e.dot(x, w)),
                                   rtol=1e-5, atol=1e-5)
        print("sharded float ok")
    """)
    assert "sharded float ok" in out


def test_sharded_stat_requires_divisible_k():
    """approx_stat's contraction-level correction is not separable per
    product, so the k-pad f(0,0) fix-up can't apply — loud error."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.nn import substrate as sub
        from repro.nn.substrate import ContractionSpec, Partitioning

        mesh = make_mesh((2, 4), ("data", "model"))
        part = Partitioning(mesh, m_axis="data", k_axis="model")
        s = sub.get_substrate("approx_stat")
        a = jnp.zeros((4, 19), jnp.int8)   # K=19 not divisible by 4
        b = jnp.zeros((19, 4), jnp.int8)
        try:
            s.dot_general(a, b, ContractionSpec(partitioning=part))
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "scalar_faithful" in str(e), e
        # divisible K works (contraction-level rounding may differ per
        # shard, so compare against tolerance, not bit-identity)
        a = jnp.asarray(np.random.default_rng(0).integers(-128, 128, (4, 32)),
                        jnp.int8)
        b = jnp.asarray(np.random.default_rng(1).integers(-128, 128, (32, 4)),
                        jnp.int8)
        ref = np.asarray(s.dot_int(a, b), np.int64)
        got = np.asarray(s.dot_general(a, b,
                                       ContractionSpec(partitioning=part)),
                         np.int64)
        assert np.abs(got - ref).max() <= 4, np.abs(got - ref).max()
        print("stat sharded ok")
    """)
    assert "stat sharded ok" in out
