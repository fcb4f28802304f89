"""Product-substrate layer: one registry for every scalar-product unit.

The paper's thesis is that a single scalar-product unit — the sign-focused-
compressor approximate multiplier — can be swapped underneath convolution and
matmul workloads. This module makes that swap a first-class object instead of
stringly-typed ``if mode == ...`` chains: a :class:`ProductSubstrate` bundles
one ``dot_general``-style contraction entry point

* ``dot_general(x, w, spec)`` — the single contraction surface. A
  :class:`ContractionSpec` carries (i) jax-style *dimension numbers*
  (batched/transposed contractions without hand reshapes), (ii) an optional
  :class:`QuantPolicy` (the float→intN quantization boundary: per-tensor vs
  per-channel scales, width, pinned scales), and (iii) an optional
  :class:`Partitioning` (mesh + axis names) that lowers the contraction
  through ``shard_map`` — data-parallel M, reduce-scattered K — while
  staying bit-identical to the unsharded path for every bit-exact backend,

plus the raw product model and the integer contraction

* ``scalar(a, b)``   — the raw intN×intN→int32 product model,
* ``dot_int(a, b)``  — 2-D integer-domain (M,K)@(K,N) contraction (exact
                       adder; operands are int8 for widths ≤ 8, int16 wider),

and :class:`SubstrateMeta` (bit-exactness, operand width, preferred
backend, cost hints) so launchers/benchmarks can reason about a substrate
without running it.

Registered backends (``list_substrates()``):

* ``exact``           — float reference dot; exact integer contraction.
* ``int8``            — symmetric int8 quantization, exact int32 matmul.
* ``approx_bitexact`` — every scalar product through the closed-form
                        multiplier model; bit-identical to the netlist.
                        Any width 3..16.
* ``approx_lut``      — same contraction through the (2^N)² product LUT.
                        Widths ≤ 8 (the table must be enumerable).
* ``approx_stat``     — exact int32 matmul + separable statistical error
                        model (MXU-friendly deployment stand-in). Widths ≤ 8
                        (the model is fit on the exhaustive error LUT).
* ``approx_pallas``   — the tiled Pallas TPU kernels (interpret mode on
                        the CPU only); bit-identical to
                        ``approx_bitexact``. Any wiring at widths 3..8:
                        CSP wirings run a *generated* closed-form VPU
                        kernel (``kernels.closed_form.make_closed_form``
                        through ``kernels/approx_matmul``); non-CSP product
                        models (``"exact"``) fall back to the LUT-input
                        kernel (``kernels/lut_matmul``). Convolutions take
                        the fused in-kernel-im2col path
                        (``kernels/fused_conv``) via ``fused_conv2d``.

Spec grammar — ``"backend[:mult_name[@N]]"`` — selects a backend, a
multiplier wiring, and an operand width at once:

* ``"approx_lut:design_du2022"`` — any name in
  ``core.multiplier.ALL_MULTIPLIERS`` (or a ``csp_*`` alias) is reachable;
* ``"approx_lut:csp_axc1@4"`` / ``"approx_bitexact:proposed@16"`` — the same
  wiring instantiated at 4- or 16-bit operand width;
* a bare backend name defaults to the paper's ``proposed`` wiring at N=8.

Width contract: ``meta.width`` is the operand width N. Integer operands
outside the signed N-bit range are **wrapped** (low N bits, sign-extended)
by every approx backend, so bitexact/LUT stay bit-identical on arbitrary
ints; the float path quantizes into range so wrapping never fires.
N=4 and N=8 models are exhaustively verified against the structural netlist
model in tests; N=16 is verified on random samples.

Accumulator contract: every integer contraction accumulates in int32 (JAX
runs without x64 here), i.e. sums are exact until they exceed ±2^31 and
wrap mod 2^32 beyond that. At N ≤ 8 no realistic K overflows; at N=16 the
worst-case product is ~2^30, so keep K·|products| below 2^31 (edge-detection
taps and quantized convs do) — ``scalar_faithful`` parity is defined modulo
2^32. int32 addition is exact and associative under that modulus, which is
why the sharded (psum / psum_scatter) reduction order cannot perturb
bit-exact backends.

NOTE: the approximate multiplier maps (0,0) → +compensation_constant(N)
(the constant fires regardless of operands — true to the netlist; +192 at
N=8), so zero padding of the contraction dimension injects spurious
contributions; every backend corrects for f(0,0) where it pads — including
per K-shard under a :class:`Partitioning`, where each shard corrects its
own local k-chunk padding and the global shard-divisibility pad is
corrected once after the reduce.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Callable, Dict, NamedTuple, Optional, Protocol, Tuple, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import lut as lut_lib
from repro.core import multiplier as mult
from repro.nn import quant
from repro.obs.meter import current_meter as _current_meter

Array = jnp.ndarray

_K_CHUNK = 16  # k-slab size for the bit-exact contraction


# ---------------------------------------------------------------------------
# Protocol + metadata
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SubstrateMeta:
    """Static facts about a substrate, for dispatch-free reasoning.

    bit_exact:        product values are bit-identical to the hardware netlist
                      (exact backends are trivially bit-exact to *their* model).
    scalar_faithful:  ``dot_int(a, b) == Σ_k scalar(a_k, b_k)`` exactly —
                      holds for everything except the statistical error model,
                      which is defined at contraction level (one rounding of
                      the separable correction per output element).
    preferred_backend: "tpu" for kernels that only pay off on real hardware,
                      "any" otherwise.
    cost_hint:        dominant execution resource: "mxu" | "vpu" | "gather" |
                      "scalar-emulation".
    width:            operand width N of the scalar-product unit (bits).
    """

    name: str
    mult_name: str
    bit_exact: bool
    scalar_faithful: bool
    preferred_backend: str
    cost_hint: str
    width: int = mult.N_BITS

    @property
    def mult_key(self) -> str:
        """Wiring + width key, as it appears in spec strings (``@8`` implicit)."""
        if self.width == mult.N_BITS:
            return self.mult_name
        return f"{self.mult_name}@{self.width}"

    @property
    def spec(self) -> str:
        return f"{self.name}:{self.mult_key}"

    @property
    def label(self) -> str:
        """Short display name: bare backend for default wirings at default
        width, full spec otherwise (keeps benchmark row names distinct)."""
        if self.mult_name in ("exact", "proposed") and self.width == mult.N_BITS:
            return self.name
        return self.spec


@runtime_checkable
class ProductSubstrate(Protocol):
    """Anything with the ``dot_general`` contraction surface + metadata."""

    meta: SubstrateMeta

    def scalar(self, a: Array, b: Array) -> Array: ...

    def dot_general(self, x: Array, w: Array,
                    spec: "Optional[ContractionSpec]" = None) -> Array: ...

    def dot_int(self, a: Array, b: Array) -> Array: ...


# ---------------------------------------------------------------------------
# Contraction policies: dimension numbers + quantization + partitioning
# ---------------------------------------------------------------------------

#: jax ``dot_general``-style dimension numbers:
#: ``((lhs_contracting, rhs_contracting), (lhs_batch, rhs_batch))``.
#: Negative axes are allowed (normalized per operand rank).
DimensionNumbers = Tuple[Tuple[Tuple[int, ...], Tuple[int, ...]],
                         Tuple[Tuple[int, ...], Tuple[int, ...]]]

#: Plain matmul dims: contract the last lhs axis with the first rhs axis —
#: valid for any lhs rank (``x @ w`` for x (..., K), w (K, N)).
MATMUL_DIMS: DimensionNumbers = (((-1,), (0,)), ((), ()))


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """Float→intN quantization boundary policy for ``dot_general``.

    Callers vary (or pin) the policy per call site; the defaults are
    ``dense()``'s.

    bits:     operand width to quantize to (None → the substrate's
              ``meta.width``; must not exceed it — wider codes would wrap in
              the narrower multiplier).
    x_mode:   activation scale granularity — ``"per_tensor"`` (one dynamic
              scalar scale, the default) or ``"per_channel"``
              (one scale per output row, i.e. per flattened lhs free
              element).
    w_mode:   weight scale granularity — ``"per_channel"`` (one scale per
              flattened rhs free element, the default) or
              ``"per_tensor"``.
    x_scale / w_scale:
              pinned scales. When set, the dynamic absmax computation is
              skipped and values quantize as ``round(v / scale)`` — this is
              how callers reuse one calibrated scale across many calls.
              Shapes broadcast against the *normalized* operand layouts:
              lhs ``(B, M, 1)`` and rhs ``(B, 1, N)`` (scalar, ``(N,)`` etc.
              all work for the plain-matmul dims).
    eps:      epsilon guard for the dynamic scale: ``scale =
              max(absmax, eps) / qmax``. Keeps all-zero operand tensors
              from producing a 0/0 scale — a zero tensor quantizes to
              zeros under a tiny-but-finite scale, so downstream output is
              exactly representable zero, not NaN.
    """

    bits: Optional[int] = None
    x_mode: str = "per_tensor"
    w_mode: str = "per_channel"
    x_scale: Optional[Array] = None
    w_scale: Optional[Array] = None
    eps: float = 1e-8

    def __post_init__(self):
        for field_name, mode in (("x_mode", self.x_mode),
                                 ("w_mode", self.w_mode)):
            if mode not in ("per_tensor", "per_channel"):
                raise ValueError(
                    f"QuantPolicy.{field_name} must be 'per_tensor' or "
                    f"'per_channel', got {mode!r}")
        if self.bits is not None and not (2 <= self.bits <= 16):
            raise ValueError(
                f"QuantPolicy.bits must be in [2, 16], got {self.bits}")
        if self.eps <= 0:
            raise ValueError(f"QuantPolicy.eps must be > 0, got {self.eps}")


@dataclasses.dataclass(frozen=True)
class Partitioning:
    """Mesh lowering policy: shard the contraction through ``shard_map``.

    m_axis: mesh axis carrying data-parallel output rows (the flattened lhs
            free dims). Rows pad up to the axis size and crop after.
    k_axis: mesh axis the contraction dim is reduce-scattered over. Each
            shard contracts its K slice locally (every backend's own
            per-shard f(0,0) k-padding correction applies *inside* the
            shard), then partial sums combine with an int32 psum_scatter
            (psum when N doesn't divide the axis). int32 addition is exact,
            so bit-exact backends stay bit-identical to the unsharded path
            regardless of reduction order. When K doesn't divide the axis
            size, the global zero-pad is corrected once with the wiring's
            f(0,0) after the reduce — only possible for scalar-faithful
            substrates (``approx_stat`` requires divisible K).

    ``approx_stat`` caveat: its separable correction rounds once per shard
    instead of once globally, so sharded results may differ from unsharded
    by the per-shard truncation (the backend is not bit_exact to begin
    with).
    """

    mesh: jax.sharding.Mesh
    m_axis: Optional[str] = "data"
    k_axis: Optional[str] = None

    def __post_init__(self):
        if self.m_axis is None and self.k_axis is None:
            raise ValueError(
                "Partitioning needs at least one of m_axis / k_axis")
        for ax in (self.m_axis, self.k_axis):
            if ax is not None and ax not in self.mesh.axis_names:
                raise ValueError(
                    f"Partitioning axis {ax!r} is not a mesh axis "
                    f"(mesh has {self.mesh.axis_names})")
        if self.m_axis is not None and self.m_axis == self.k_axis:
            raise ValueError(
                f"m_axis and k_axis must differ, both are {self.m_axis!r}")

    @property
    def m_shards(self) -> int:
        return int(self.mesh.shape[self.m_axis]) if self.m_axis else 1

    @property
    def k_shards(self) -> int:
        return int(self.mesh.shape[self.k_axis]) if self.k_axis else 1


@dataclasses.dataclass(frozen=True)
class ContractionSpec:
    """Everything ``dot_general`` needs beyond the two operands.

    dimension_numbers: jax ``dot_general`` style (negative axes allowed).
                       Output layout matches ``jax.lax.dot_general``:
                       ``(batch..., lhs_free..., rhs_free...)``.
    quant:             None → integer-domain contraction (operands must be
                       integers); a :class:`QuantPolicy` → float operands
                       through the quantization boundary.
    partitioning:      None → single-device contraction; a
                       :class:`Partitioning` → lowered through shard_map.
    site:              optional contraction-site name (``"layer.3.attn.wq"``,
                       ``"conv.edge.center"`` — see :mod:`repro.nn.plan`);
                       purely observational: the telemetry meter attributes
                       MAC/energy counts to it instead of the shape label.
    """

    dimension_numbers: DimensionNumbers = MATMUL_DIMS
    quant: Optional[QuantPolicy] = None
    partitioning: Optional[Partitioning] = None
    site: Optional[str] = None

    @staticmethod
    def matmul(quant: Optional[QuantPolicy] = None,
               partitioning: Optional[Partitioning] = None,
               site: Optional[str] = None) -> "ContractionSpec":
        """Plain ``(…, K) @ (K, N)`` spec."""
        return ContractionSpec(MATMUL_DIMS, quant, partitioning, site)


# -- ambient partitioning (opt-in mesh lowering for deep call sites) --------

_PART_STATE = threading.local()


def current_partitioning() -> Optional[Partitioning]:
    """The ambient :class:`Partitioning` installed by
    :func:`partitioning_scope`, or None. Read at *trace* time by call sites
    that cannot thread a spec explicitly (``models.common.dense``)."""
    return getattr(_PART_STATE, "value", None)


@contextlib.contextmanager
def partitioning_scope(p: Optional[Partitioning]):
    """Install an ambient Partitioning for the duration of the block.

    Lets a sharded caller lower every model ``dense`` contraction through
    shard_map without threading a spec through the whole model zoo.
    ``None`` is a no-op scope.
    """
    prev = getattr(_PART_STATE, "value", None)
    _PART_STATE.value = p
    try:
        yield p
    finally:
        _PART_STATE.value = prev


# -- ambient contraction override (the QAT layer's injection point) ---------

_DOT_OVERRIDE_STATE = threading.local()


def current_dot_override():
    """The ambient contraction override installed by
    :func:`dot_override_scope`, or None. Read at *trace* time by call sites
    that route through the ambient plan (``models.common.dense``)."""
    return getattr(_DOT_OVERRIDE_STATE, "value", None)


@contextlib.contextmanager
def dot_override_scope(fn):
    """Install an ambient contraction override for the duration of the block.

    ``fn(spec_str, x, w, cspec) -> Array`` replaces the default
    ``get_substrate(spec_str).dot_general(x, w, cspec)`` at every consulting
    call site. The hook exists so higher layers can change *how* a resolved
    (site → spec) assignment contracts without the nn layer importing them —
    ``repro.train.qat.qat_scope`` installs its straight-through-estimator
    wrapper here, keeping forward values bit-identical to the substrate
    while making the contraction differentiable. ``None`` is a no-op scope.
    Thread-local, like :func:`partitioning_scope`.
    """
    prev = getattr(_DOT_OVERRIDE_STATE, "value", None)
    _DOT_OVERRIDE_STATE.value = fn
    try:
        yield fn
    finally:
        _DOT_OVERRIDE_STATE.value = prev


# ---------------------------------------------------------------------------
# Dimension-number normalization + contraction planning
# ---------------------------------------------------------------------------


def _norm_axes(axes, ndim: int, what: str) -> Tuple[int, ...]:
    out = []
    for d in axes:
        d = int(d)
        if not -ndim <= d < ndim:
            raise ValueError(
                f"{what} dimension {d} out of range for rank-{ndim} operand")
        out.append(d % ndim)
    if len(set(out)) != len(out):
        raise ValueError(f"duplicate {what} dimensions: {tuple(axes)}")
    return tuple(out)


class _Plan(NamedTuple):
    """Precomputed transposes/reshapes taking arbitrary dimension numbers to
    the canonical batched 2-D form ``(B, M, K) @ (B, K, N) -> (B, M, N)``."""

    dims: DimensionNumbers          # normalized (non-negative) numbers
    lhs_perm: Tuple[int, ...]
    rhs_perm: Tuple[int, ...]
    b: int
    m: int
    k: int
    n: int
    out_shape: Tuple[int, ...]

    def lhs3(self, x: Array) -> Array:
        return x.transpose(self.lhs_perm).reshape(self.b, self.m, self.k)

    def rhs3(self, w: Array) -> Array:
        return w.transpose(self.rhs_perm).reshape(self.b, self.k, self.n)

    def unflatten(self, out3: Array) -> Array:
        return out3.reshape(self.out_shape)


def _plan_contraction(lhs_shape, rhs_shape,
                      dimension_numbers: DimensionNumbers) -> _Plan:
    try:
        (lc, rc), (lb, rb) = dimension_numbers
    except (TypeError, ValueError) as e:
        raise ValueError(
            "dimension_numbers must be ((lhs_contracting, rhs_contracting), "
            f"(lhs_batch, rhs_batch)); got {dimension_numbers!r}") from e
    lnd, rnd = len(lhs_shape), len(rhs_shape)
    lc = _norm_axes(lc, lnd, "lhs contracting")
    rc = _norm_axes(rc, rnd, "rhs contracting")
    lb = _norm_axes(lb, lnd, "lhs batch")
    rb = _norm_axes(rb, rnd, "rhs batch")
    if len(lc) != len(rc) or len(lb) != len(rb):
        raise ValueError(
            f"contracting/batch dimension lists must pair up: "
            f"lhs {lc}/{lb} vs rhs {rc}/{rb}")
    if set(lc) & set(lb) or set(rc) & set(rb):
        raise ValueError(
            "a dimension cannot be both contracting and batch: "
            f"lhs {lc}∩{lb}, rhs {rc}∩{rb}")
    for dl, dr in zip(lc, rc):
        if lhs_shape[dl] != rhs_shape[dr]:
            raise ValueError(
                f"contracting dimension mismatch: lhs dim {dl} has size "
                f"{lhs_shape[dl]}, rhs dim {dr} has size {rhs_shape[dr]}")
    for dl, dr in zip(lb, rb):
        if lhs_shape[dl] != rhs_shape[dr]:
            raise ValueError(
                f"batch dimension mismatch: lhs dim {dl} has size "
                f"{lhs_shape[dl]}, rhs dim {dr} has size {rhs_shape[dr]}")
    lfree = tuple(d for d in range(lnd) if d not in lc and d not in lb)
    rfree = tuple(d for d in range(rnd) if d not in rc and d not in rb)
    prod = lambda dims, shape: int(np.prod([shape[d] for d in dims],
                                           dtype=np.int64)) if dims else 1
    out_shape = tuple([lhs_shape[d] for d in lb]
                      + [lhs_shape[d] for d in lfree]
                      + [rhs_shape[d] for d in rfree])
    return _Plan(
        dims=((lc, rc), (lb, rb)),
        lhs_perm=lb + lfree + lc,
        rhs_perm=rb + rc + rfree,
        b=prod(lb, lhs_shape), m=prod(lfree, lhs_shape),
        k=prod(lc, lhs_shape), n=prod(rfree, rhs_shape),
        out_shape=out_shape,
    )


def _quantize_operand(t3: Array, mode: str, pinned_scale, contract_axis: int,
                      bits: int, eps: float):
    """Quantize a normalized ``(B, ·, ·)`` operand per the policy.

    Returns (int values in the width's storage dtype, f32 scale). The
    dynamic branch is ``quant.quantize`` — whose scale is epsilon-guarded:
    an all-zero tensor gets a tiny finite scale, so its quantized values
    and the dequantized output are exactly zero instead of NaN (regression:
    zero image → zero edge map through the float path). A pinned scale
    skips the absmax and quantizes as ``round(v / scale)``.
    """
    if pinned_scale is None:
        axes = None if mode == "per_tensor" else (contract_axis,)
        q = quant.quantize(t3, axes=axes, bits=bits, eps=eps)
        return q.values, q.scale
    qm = quant.qmax(bits)
    scale = jnp.asarray(pinned_scale, jnp.float32)
    q = jnp.clip(jnp.round(t3.astype(jnp.float32) / scale), -qm, qm)
    return q.astype(quant.storage_dtype(bits)), scale


# ---------------------------------------------------------------------------
# Shared contraction machinery
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _stat_tables(mult_key: str) -> tuple[np.ndarray, np.ndarray, float]:
    """Separable error model (r[a], c[b], µ) from the width-N error LUT."""
    e = lut_lib.error_lut(mult_key).astype(np.float64)
    mu = e.mean()
    r = e.mean(axis=1) - 0.5 * mu
    c = e.mean(axis=0) - 0.5 * mu
    return r.astype(np.float32), c.astype(np.float32), float(mu)


def _bitexact_contract(a8: Array, b8: Array, product_fn,
                       f00: int | None = None) -> Array:
    """sum_k f(a[m,k], b[k,n]) with f an arbitrary intN×intN→int32 model.

    ``f00``: the model's f(0,0) value, needed to correct k-padding. Callers
    that know it statically pass it so the contraction stays traceable (the
    serving path jits whole ``edge_detect_batched`` calls through here);
    when omitted it is constant-folded out of the trace.
    """
    m, k = a8.shape
    k2, n = b8.shape
    assert k == k2, (a8.shape, b8.shape)
    pad = (-k) % _K_CHUNK
    if pad:
        # pad with zeros, then subtract the spurious f(0,0) contributions
        a8 = jnp.pad(a8, ((0, 0), (0, pad)))
        b8 = jnp.pad(b8, ((0, pad), (0, 0)))
    steps = a8.shape[1] // _K_CHUNK
    a3 = a8.reshape(m, steps, _K_CHUNK).transpose(1, 0, 2).astype(jnp.int32)
    b3 = b8.reshape(steps, _K_CHUNK, n).astype(jnp.int32)

    def body(acc, slabs):
        a_c, b_c = slabs  # (m, ck), (ck, n)
        prod = product_fn(a_c[:, :, None], b_c[None, :, :])  # (m, ck, n)
        return acc + prod.sum(axis=1), None

    acc0 = jnp.zeros((m, n), jnp.int32)
    acc, _ = jax.lax.scan(body, acc0, (a3, b3))
    if pad:
        if f00 is None:
            with jax.ensure_compile_time_eval():
                f00 = int(product_fn(jnp.zeros((), jnp.int32),
                                     jnp.zeros((), jnp.int32)))
        acc = acc - f00 * pad
    return acc


def _exact_int_matmul(a8: Array, b8: Array) -> Array:
    return jax.lax.dot_general(
        a8, b8, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def _sharded_dot(local_dot, a: Array, b: Array, part: Partitioning,
                 k_pad_unit: Optional[int]) -> Array:
    """(M,K)@(K,N) through shard_map: one lowering for int and float.

    Data-parallel M over ``part.m_axis``; K reduce-scattered over
    ``part.k_axis`` — each shard runs ``local_dot`` on its K slice (a
    substrate's own per-shard f(0,0) k-chunk-padding correction applies
    locally inside it), then partial sums combine via psum_scatter over the
    output's N dim when it divides the axis, plain psum otherwise (the
    output stays replicated over k). ``k_pad_unit`` is what one zero-padded
    K element contributes to every output (the wiring's f(0,0) for approx
    models, 0 for exact paths): global shard-divisibility zero-padding of K
    is corrected once with it after the reduce; None means no such
    correction exists, so non-divisible K must raise before calling here.
    """
    from jax.sharding import PartitionSpec as P

    m, k = a.shape
    _, n = b.shape
    pm = (-m) % part.m_shards
    pk = (-k) % part.k_shards
    assert not (pk and k_pad_unit is None), "caller must reject this"
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk:
        b = jnp.pad(b, ((0, pk), (0, 0)))
    scatter = part.k_axis is not None and n % part.k_shards == 0

    def body(al, bl):
        out = local_dot(al, bl)
        if part.k_axis is not None:
            if scatter:
                out = jax.lax.psum_scatter(out, part.k_axis,
                                           scatter_dimension=1, tiled=True)
            else:
                out = jax.lax.psum(out, part.k_axis)
        return out

    out = jax.shard_map(
        body, mesh=part.mesh,
        in_specs=(P(part.m_axis, part.k_axis), P(part.k_axis, None)),
        out_specs=P(part.m_axis, part.k_axis if scatter else None),
        check_vma=False,
    )(a, b)
    if pk and k_pad_unit:
        out = out - k_pad_unit * pk
    return out[:m] if pm else out


def _sharded_dot_int(substrate: "_SubstrateBase", a: Array, b: Array,
                     part: Partitioning) -> Array:
    """Integer ``_sharded_dot``: exact int32 reduce, f(0,0) pad unit."""
    k = a.shape[1]
    if substrate._f00 is None and k % part.k_shards:
        raise ValueError(
            f"{substrate.meta.spec}: K={k} must be a multiple of the k_axis "
            f"size ({part.k_shards}) — this substrate's correction is defined "
            "at contraction level (scalar_faithful=False), so the k-pad "
            "f(0,0) fix-up does not apply; pad K yourself or drop k_axis")
    return _sharded_dot(substrate.dot_int, a, b, part, substrate._f00)


def _sharded_dot_float(a: Array, b: Array, part: Partitioning) -> Array:
    """Float ``_sharded_dot`` (exact backend's mesh path): zero k-padding
    is exact in float, but the psum reduction order makes this ≈ (not
    bit-identical to) the unsharded float dot, as usual for float."""
    return _sharded_dot(jnp.matmul, a, b, part, k_pad_unit=0)


class _SubstrateBase:
    """Shared ``dot_general`` plumbing."""

    meta: SubstrateMeta
    #: the scalar-product model's f(0,0) — the k-padding correction unit.
    #: 0 for exact backends, the wiring's compensation value for approx
    #: ones, None where no per-product value exists (approx_stat).
    _f00: Optional[int] = 0

    # -- raw product model ---------------------------------------------------

    def scalar(self, a: Array, b: Array) -> Array:
        raise NotImplementedError

    def dot_int(self, a: Array, b: Array) -> Array:
        """2-D (M,K)@(K,N) integer contraction (exact int32 adder)."""
        raise NotImplementedError

    def _stor(self, x: Array) -> Array:
        """Cast integer operands to the width's storage dtype (int8/int16)."""
        return jnp.asarray(x, quant.storage_dtype(self.meta.width))

    # -- telemetry -----------------------------------------------------------

    def _meter_hook(self, plan: "_Plan", a3: Optional[Array],
                    b3: Optional[Array],
                    site: Optional[str] = None) -> None:
        """Record this contraction on the ambient telemetry meter, if any.

        One global read when no :func:`repro.obs.meter.telemetry_scope`
        is active — the metered path is purely additive (counts / MACs /
        estimated energy, plus the opt-in error probe on integer
        operands), so outputs are bit-identical either way. ``site`` (from
        ``spec.site``) names the contraction site for per-site attribution.
        """
        meter = _current_meter()
        if meter is None:
            return
        meter.record_contraction(self.meta, plan.b, plan.m, plan.k, plan.n,
                                 site=site)
        if (meter.error_probe and a3 is not None
                and self.meta.mult_name != "exact"
                and jnp.issubdtype(a3.dtype, jnp.integer)):
            meter.probe(self.meta, self.scalar, a3, b3, site=site)

    # -- the contraction surface ---------------------------------------------

    def dot_general(self, x: Array, w: Array,
                    spec: Optional[ContractionSpec] = None) -> Array:
        """General contraction of ``x`` and ``w`` under this substrate.

        ``spec`` (default :class:`ContractionSpec`, i.e. plain matmul dims,
        integer domain, unpartitioned) carries dimension numbers, the
        quantization policy, and the mesh partitioning — see the class
        docstrings. Output layout matches ``jax.lax.dot_general``:
        ``(batch..., lhs_free..., rhs_free...)``.
        """
        spec = spec if spec is not None else ContractionSpec()
        x = jnp.asarray(x)
        w = jnp.asarray(w)
        plan = _plan_contraction(x.shape, w.shape, spec.dimension_numbers)
        if spec.quant is None:
            if not (jnp.issubdtype(x.dtype, jnp.integer)
                    and jnp.issubdtype(w.dtype, jnp.integer)):
                raise TypeError(
                    "integer-domain dot_general (spec.quant=None) needs "
                    f"integer operands, got {x.dtype}/{w.dtype}; pass a "
                    "QuantPolicy to contract float tensors")
            a3, b3 = plan.lhs3(x), plan.rhs3(w)
            self._meter_hook(plan, a3, b3, site=spec.site)
            out3 = self._contract3(a3, b3, spec.partitioning)
            return plan.unflatten(out3)
        q = spec.quant
        bits = q.bits if q.bits is not None else self.meta.width
        if bits > self.meta.width:
            raise ValueError(
                f"QuantPolicy.bits={bits} exceeds the substrate operand "
                f"width {self.meta.width} ({self.meta.spec}) — wider codes "
                "would wrap in the narrower multiplier")
        qa, sa = _quantize_operand(plan.lhs3(x), q.x_mode, q.x_scale,
                                   contract_axis=2, bits=bits, eps=q.eps)
        qb, sb = _quantize_operand(plan.rhs3(w), q.w_mode, q.w_scale,
                                   contract_axis=1, bits=bits, eps=q.eps)
        self._meter_hook(plan, qa, qb, site=spec.site)
        out3 = self._contract3(qa, qb, spec.partitioning)
        out3 = out3.astype(jnp.float32) * (sa * sb)
        return plan.unflatten(out3).astype(x.dtype)

    def _contract3(self, a3: Array, b3: Array,
                   partitioning: Optional[Partitioning]) -> Array:
        """(B,M,K)@(B,K,N) via the backend 2-D kernel (vmap over batch)."""
        if a3.shape[0] == 1:
            return self._contract2(a3[0], b3[0], partitioning)[None]
        if partitioning is not None:
            raise NotImplementedError(
                "partitioned dot_general with batch dimensions is not "
                "supported yet — shard the batch outside, or drop "
                "spec.partitioning")
        return jax.vmap(self.dot_int)(a3, b3)

    def _contract2(self, a: Array, b: Array,
                   partitioning: Optional[Partitioning]) -> Array:
        if partitioning is None:
            return self.dot_int(a, b)
        return _sharded_dot_int(self, a, b, partitioning)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<{type(self).__name__} {self.meta.spec}>"


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------


def _reject_wiring(backend: str, mult_name: str | None) -> None:
    """Exact backends take no multiplier wiring — a suffix is a confused
    spec (e.g. ``"int8:design_du2022"`` meaning approx_*), not a no-op."""
    if mult_name not in (None, "exact"):
        raise ValueError(
            f"{backend} is an exact backend and takes no multiplier wiring "
            f"(got {mult_name!r}); use approx_bitexact/approx_lut/approx_stat "
            "to select a wiring.")


def _split_suffix(mult_name: str | None) -> tuple[str, int]:
    """Wiring suffix (possibly carrying ``@N``) → (base_name, width).

    An empty wiring name in front of a width (``"@4"``) is rejected, not
    defaulted: a config typo that drops the wiring but keeps ``@N`` would
    otherwise silently run the proposed design instead of the intended one.
    """
    base, n = mult.split_width(mult_name or "proposed")
    if not base:
        raise ValueError(
            f"malformed multiplier suffix {mult_name!r}: a width needs a "
            "wiring name (mult_name[@N]), e.g. 'proposed@4'")
    return base, n


class ExactSubstrate(_SubstrateBase):
    """Float reference: plain dot in the compute dtype, exact int contraction.

    The float path ignores the :class:`QuantPolicy` — this backend *is* the
    unquantized reference the quantized substrates are compared against.
    """

    def __init__(self, mult_name: str | None = None):
        _reject_wiring("exact", mult_name)
        self._f00 = 0
        self.meta = SubstrateMeta("exact", "exact", bit_exact=True,
                                  scalar_faithful=True, preferred_backend="any",
                                  cost_hint="mxu")

    def scalar(self, a, b):
        return mult.exact_multiply(a, b)

    def dot_int(self, a, b):
        return _exact_int_matmul(self._stor(a), self._stor(b))

    def dot_general(self, x, w, spec: Optional[ContractionSpec] = None):
        spec = spec if spec is not None else ContractionSpec()
        x = jnp.asarray(x)
        if spec.quant is not None:
            # the quantization boundary is a no-op here by definition:
            # contract in the compute dtype
            w = jnp.asarray(w, x.dtype)
            plan = _plan_contraction(x.shape, w.shape, spec.dimension_numbers)
            self._meter_hook(plan, None, None, site=spec.site)  # no probe
            if spec.partitioning is None:
                return jax.lax.dot_general(x, w, plan.dims)
            if plan.b != 1:
                raise NotImplementedError(
                    "partitioned dot_general with batch dimensions is not "
                    "supported yet")
            out3 = _sharded_dot_float(plan.lhs3(x)[0], plan.rhs3(w)[0],
                                      spec.partitioning)[None]
            return plan.unflatten(out3)
        return super().dot_general(x, w, spec)


class Int8Substrate(_SubstrateBase):
    """Symmetric int8 quantization boundary, exact int32 matmul."""

    def __init__(self, mult_name: str | None = None):
        _reject_wiring("int8", mult_name)
        self._f00 = 0
        self.meta = SubstrateMeta("int8", "exact", bit_exact=True,
                                  scalar_faithful=True, preferred_backend="any",
                                  cost_hint="mxu")

    def scalar(self, a, b):
        return mult.exact_multiply(a, b)

    def dot_int(self, a, b):
        return _exact_int_matmul(self._stor(a), self._stor(b))


class BitexactSubstrate(_SubstrateBase):
    """Every scalar product through the closed-form multiplier model.

    Supports any wiring at any width 3..16 (``"proposed@16"`` etc.)."""

    def __init__(self, mult_name: str | None = None):
        base, n = _split_suffix(mult_name)
        _, self._fn, n = mult.resolve_multiplier(base, n)
        with jax.ensure_compile_time_eval():
            self._f00 = int(self._fn(jnp.zeros((), jnp.int32),
                                     jnp.zeros((), jnp.int32)))
        self.meta = SubstrateMeta("approx_bitexact", base, bit_exact=True,
                                  scalar_faithful=True, preferred_backend="any",
                                  cost_hint="scalar-emulation", width=n)

    def scalar(self, a, b):
        return self._fn(a, b)

    def dot_int(self, a, b):
        return _bitexact_contract(self._stor(a), self._stor(b), self._fn,
                                  f00=self._f00)


class LutSubstrate(_SubstrateBase):
    """Gather-based contraction through the (2^N)² product LUT (N ≤ 8)."""

    def __init__(self, mult_name: str | None = None):
        base, n = _split_suffix(mult_name)
        key, _, n = mult.resolve_multiplier(base, n)
        if n > lut_lib.MAX_LUT_BITS:
            raise ValueError(
                f"approx_lut needs an enumerable product table (width <= "
                f"{lut_lib.MAX_LUT_BITS}, got {n}); use approx_bitexact for "
                "wider operands")
        self._key = key
        self._f00 = int(lut_lib.f00(key))
        self.meta = SubstrateMeta("approx_lut", base, bit_exact=True,
                                  scalar_faithful=True, preferred_backend="any",
                                  cost_hint="gather", width=n)

    def _table(self) -> Array:
        return jnp.asarray(lut_lib.build_lut(self._key))

    def scalar(self, a, b):
        return lut_lib.lut_multiply(a, b, self._table())

    def dot_int(self, a, b):
        table = self._table()
        n = self.meta.width
        size, off = 1 << n, 1 << (n - 1)
        return _bitexact_contract(
            self._stor(a), self._stor(b),
            lambda x, y: table[(x + off) & (size - 1), (y + off) & (size - 1)],
            f00=self._f00)


class StatSubstrate(_SubstrateBase):
    """Exact int32 matmul + separable statistical error model.

    E[e(a,b)] ≈ r[a] + c[b] − µ, where e is the multiplier's error LUT and
    r/c its row/column means. Adds two gathers + two rank-1 terms, lowers to
    MXU-friendly HLO: a deployment-scale stand-in for the multiplier (the
    Pallas kernel is the bit-exact path on real hardware).
    Beyond-paper contribution. The correction is defined at contraction level
    (``scalar_faithful=False``): ``dot_int`` rounds the summed correction
    once per output element, while ``scalar`` rounds per product. Widths ≤ 8
    (the separable model is fit on the exhaustive error LUT).
    """

    def __init__(self, mult_name: str | None = None):
        base, n = _split_suffix(mult_name)
        key, _, n = mult.resolve_multiplier(base, n)
        if n > lut_lib.MAX_LUT_BITS:
            raise ValueError(
                "approx_stat fits its separable error model on the "
                f"exhaustive error LUT (width <= {lut_lib.MAX_LUT_BITS}, "
                f"got {n}); use approx_bitexact for wider operands")
        self._key = key
        self._f00 = None  # the correction is not separable per product
        self.meta = SubstrateMeta("approx_stat", base, bit_exact=False,
                                  scalar_faithful=False, preferred_backend="any",
                                  cost_hint="mxu", width=n)

    def scalar(self, a, b):
        n = self.meta.width
        off = 1 << (n - 1)
        r, c, _mu = _stat_tables(self._key)
        a = mult.wrap_operand(jnp.asarray(a, jnp.int32), n)
        b = mult.wrap_operand(jnp.asarray(b, jnp.int32), n)
        corr = jnp.asarray(r)[a + off] + jnp.asarray(c)[b + off]
        return a * b + corr.astype(jnp.int32)

    def dot_int(self, a, b):
        n = self.meta.width
        off = 1 << (n - 1)
        # wrap into the width's operand domain first (module contract) so
        # both the exact matmul and the correction gathers see the same
        # operands the scalar model does
        aw = mult.wrap_operand(jnp.asarray(a, jnp.int32), n)
        bw = mult.wrap_operand(jnp.asarray(b, jnp.int32), n)
        # wrapped values fit the storage dtype (width ≤ 8 here), so the
        # contraction keeps the int8 MXU path
        exact = _exact_int_matmul(self._stor(aw), self._stor(bw))
        r, c, _mu = _stat_tables(self._key)
        ra = jnp.asarray(r)[aw + off].sum(axis=1)  # (m,)
        cb = jnp.asarray(c)[bw + off].sum(axis=0)  # (n,)
        corr = ra[:, None] + cb[None, :]
        return exact + corr.astype(jnp.int32)


class PallasSubstrate(_SubstrateBase):
    """Tiled Pallas TPU contraction for any wiring at widths 3..8.

    Two kernel strategies behind one spec family, both bit-identical to
    ``approx_bitexact`` at the same wiring/width and both running in
    interpret mode off-TPU so the code path is testable on CPU:

    * ``"closed_form"`` — the wiring's *generated* closed form
      (``kernels.closed_form.make_closed_form``), pure VPU integer algebra
      through the vectorized-k-slab ``kernels/approx_matmul`` (cost hint
      ``vpu``). The default for every CSP wiring at every width 3..8 —
      non-proposed wirings no longer pay a per-product gather.
    * ``"lut"`` — the LUT-input kernel (``kernels/lut_matmul``): each
      product is the wiring's table entry, selected by int8 one-hot
      matmuls on the MXU against the VMEM-resident table (cost hint
      ``mxu``). The
      automatic fallback for product models with no CSP closed form
      (``"exact"``); forceable with ``kernel="lut"`` for A/B benchmarks.

    Convolutions additionally expose :meth:`fused_conv2d` — the fused
    in-kernel-im2col conv (``kernels/fused_conv``) that
    ``nn.conv.conv2d_batched`` auto-selects as its fast path.

    Widths above ``MAX_LUT_BITS`` are rejected — f(0,0) bookkeeping and
    the LUT fallback need an enumerable product table; use
    ``approx_bitexact`` for wider operands.
    """

    def __init__(self, mult_name: str | None = None, kernel: str = "auto"):
        base, n = _split_suffix(mult_name)
        key, _, n = mult.resolve_multiplier(base, n)
        if n > lut_lib.MAX_LUT_BITS:
            raise ValueError(
                "approx_pallas needs an enumerable product table for its "
                f"LUT kernel (width <= {lut_lib.MAX_LUT_BITS}, got {n}); "
                "use approx_bitexact for wider operands")
        if kernel not in ("auto", "closed_form", "lut"):
            raise ValueError(
                f"unknown approx_pallas kernel strategy {kernel!r} "
                "(known: auto, closed_form, lut)")
        self._key = key
        self._f00 = int(lut_lib.f00(key))
        self._product_fn = None
        if kernel in ("auto", "closed_form"):
            from repro.kernels.closed_form import make_closed_form

            try:
                self._product_fn = make_closed_form(key)
            except ValueError:  # no CSP structure (e.g. "exact")
                if kernel == "closed_form":
                    raise
        self._kernel_kind = "closed_form" if self._product_fn else "lut"
        self.meta = SubstrateMeta(
            "approx_pallas", base, bit_exact=True, scalar_faithful=True,
            preferred_backend="tpu",
            cost_hint="vpu" if self._product_fn else "mxu", width=n)

    def _table(self) -> Array:
        return jnp.asarray(lut_lib.flat_lut(self._key))

    def scalar(self, a, b):
        if self._product_fn is not None:
            return self._product_fn(a, b)
        return lut_lib.lut_multiply(
            a, b, jnp.asarray(lut_lib.build_lut(self._key)))

    def dot_int(self, a, b):
        a = jnp.asarray(a, jnp.int32)
        b = jnp.asarray(b, jnp.int32)
        if self._product_fn is not None:
            from repro.kernels.approx_matmul.ops import closed_form_matmul

            return closed_form_matmul(a, b, self._key)
        from repro.kernels.lut_matmul.ops import lut_matmul

        return lut_matmul(a, b, self._table())

    def fused_conv2d(self, imgs: Array, kernel: Array) -> Array:
        """Fused in-kernel-im2col conv (``kernels/fused_conv``): batched
        'same' conv with no host-side patch tensor, bit-identical to the
        im2col + ``dot_general`` path. The kernel taps must be concrete
        (they specialize the Pallas kernel) — ``conv.conv2d_batched``
        guards this and falls back to im2col for traced kernels."""
        from repro.kernels.fused_conv.ops import fused_conv2d

        return fused_conv2d(imgs, kernel, self._key,
                            kernel_kind=self._kernel_kind)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_FACTORIES: Dict[str, Callable[[str], ProductSubstrate]] = {}


def register_substrate(name: str,
                       factory: Callable[..., ProductSubstrate]) -> None:
    """Register a backend under ``name``; factory takes a mult suffix (or
    ``None`` when the spec carried no wiring — each backend applies its own
    default or rejects)."""
    _FACTORIES[name] = factory


def list_substrates() -> list[str]:
    """Registered backend names (stable order)."""
    return sorted(_FACTORIES)


class SpecParts(NamedTuple):
    """Parsed ``"backend[:mult_name[@N]]"`` spec string."""

    backend: str
    mult_name: str
    width: int


def _split_spec(spec: str) -> tuple[str, str | None]:
    """Validated ``"backend[:mult_name[@N]]"`` split → (backend, suffix).

    Rejects malformed specs instead of silently normalizing them: an empty
    backend or wiring suffix (``"exact:"``, ``":proposed"``) and any
    whitespace (``"approx_pallas:proposed@8 "``) are grammar errors — a
    stray character in a config would otherwise parse as a different,
    well-formed spec.
    """
    s = str(spec)
    if not s or any(c.isspace() for c in s):
        raise ValueError(
            f"malformed substrate spec {spec!r}: specs follow "
            "backend[:mult_name[@N]] with no whitespace")
    name, sep, suffix = s.partition(":")
    if not name or (sep and not suffix):
        part = "backend" if not name else "wiring suffix"
        raise ValueError(
            f"malformed substrate spec {spec!r}: empty {part} — specs "
            "follow backend[:mult_name[@N]]")
    return name, (suffix if sep else None)


def parse_spec(spec: str) -> SpecParts:
    """``"backend[:mult_name[@N]]"`` → (backend, mult_name, width).

    A missing wiring reads as ``"proposed"`` (the approx backends' default;
    exact backends take no wiring at all); a missing width as 8. Malformed
    specs (empty parts — including an empty wiring name before ``@N`` —
    and whitespace) raise ``ValueError``.
    """
    name, suffix = _split_spec(spec)
    base, width = mult.split_width(suffix or "proposed")
    if not base:
        raise ValueError(
            f"malformed substrate spec {spec!r}: empty wiring name before "
            "'@' — specs follow backend[:mult_name[@N]]")
    return SpecParts(name, base, width)


@functools.lru_cache(maxsize=None)
def get_substrate(spec: str = "exact",
                  mult_name: str | None = None) -> ProductSubstrate:
    """Resolve a spec string to a (cached) substrate instance.

    ``spec`` may carry a wiring+width suffix (``"approx_lut:design_du2022"``,
    ``"approx_bitexact:proposed@16"``); an explicit ``mult_name`` argument
    (which may itself carry ``@N``) overrides the suffix. Backends validate
    the wiring and width: approx backends default a missing wiring to
    ``"proposed"`` at width 8, exact backends reject any suffix outright.
    """
    name, suffix = _split_spec(spec)
    if name not in _FACTORIES:
        raise ValueError(
            f"unknown product substrate: {name!r} (known: {list_substrates()})")
    return _FACTORIES[name](mult_name or suffix or None)


def as_substrate(s: "str | ProductSubstrate") -> ProductSubstrate:
    """Accept either a spec string or an already-resolved substrate."""
    if isinstance(s, str):
        return get_substrate(s)
    return s


register_substrate("exact", ExactSubstrate)
register_substrate("int8", Int8Substrate)
register_substrate("approx_bitexact", BitexactSubstrate)
register_substrate("approx_lut", LutSubstrate)
register_substrate("approx_stat", StatSubstrate)
register_substrate("approx_pallas", PallasSubstrate)
