"""2-D convolution under the approximate multiplier (paper §4).

The paper's application: 3×3 Laplacian edge detection where every
pixel×coefficient product runs through the proposed approximate signed
multiplier, followed by exact accumulation (the MAC's adder tree is exact).

Two execution paths:

* :func:`conv2d_int` — the reference single-image Python double-loop over
  kernel taps, taking an arbitrary scalar-product function (kept as the
  parity oracle for the batched path);
* :func:`conv2d_batched` — batched NHW(C) 'same' convolution lowered to a
  single im2col + substrate contraction, so every registered
  :class:`~repro.nn.substrate.ProductSubstrate` (including the Pallas
  kernel) runs edge detection under one parity contract.

Pixels are mapped to the signed operand domain of the substrate's width by
an arithmetic shift (0..255 → 0..2^(N-1)-1; ``>> 1`` at the default N=8),
matching the fixed-point convention of approximate-multiplier papers;
kernel coefficients must fit the signed N-bit operand range (coefficients
outside it wrap, per the multipliers' two's-complement operand contract —
the Laplacian's center tap 8 wraps to −8 at N=4). Edge maps are rescaled
back to the 8-bit output range before clipping, so PSNR is comparable
across widths.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multiplier as mult

Array = jnp.ndarray

LAPLACIAN = np.array([[-1, -1, -1], [-1, 8, -1], [-1, -1, -1]], dtype=np.int32)


def to_signed_pixels(img: Array, n: int = 8) -> Array:
    """uint8 image(s) (0..255) → signed n-bit operand domain (0..2^(n-1)-1)."""
    x = jnp.asarray(img, jnp.int32)
    return (x >> (9 - n)) if n <= 9 else (x << (n - 9))


def _rescale_raw(raw: Array, n: int) -> Array:
    """Map a width-n conv response back to the 8-bit output range.

    Pixels scale as 2^(n-8) relative to the n=8 harness, so the response
    rescales by 2^(8-n); identity at the default width.
    """
    if n == 8:
        return raw
    return (raw << (8 - n)) if n < 8 else (raw >> (n - 8))


def conv2d_int(img: Array, kernel: Array,
               product_fn: Callable[[Array, Array], Array]) -> Array:
    """Zero-padded 'same' 2-D convolution with a custom scalar product.

    img: (H, W) int32 in [-128, 127]; kernel: (kh, kw) int32 in [-128, 127].
    Accumulation is exact int32 (the MAC adder is exact in the paper).
    Reference implementation — the batched pipeline is :func:`conv2d_batched`.
    """
    kh, kw = kernel.shape
    ph, pw = kh // 2, kw // 2
    x = jnp.pad(jnp.asarray(img, jnp.int32), ((ph, ph), (pw, pw)))
    h, w = img.shape
    out = jnp.zeros((h, w), jnp.int32)
    for di in range(kh):
        for dj in range(kw):
            coeff = kernel[di, dj]
            patch = jax.lax.dynamic_slice(x, (di, dj), (h, w))
            out = out + product_fn(patch, jnp.full((), int(coeff), jnp.int32))
    return out


def _im2col(imgs: Array, kh: int, kw: int) -> Array:
    """(B, H, W) int32, zero 'same' padding → (B, H, W, kh·kw) tap patches."""
    b, h, w = imgs.shape
    ph, pw = kh // 2, kw // 2
    x = jnp.pad(imgs, ((0, 0), (ph, ph), (pw, pw)))
    cols = [jax.lax.dynamic_slice(x, (0, di, dj), (b, h, w))
            for di in range(kh) for dj in range(kw)]
    return jnp.stack(cols, axis=-1)


# im2col patches are (B, H, W, taps); contract the tap axis with the
# flattened kernel — dot_general handles the free dims, no hand 2-D reshape
_CONV_DIMS = (((3,), (0,)), ((), ()))


def _meter_fused(s, imgs: Array, kernel_arr: Array, site=None) -> None:
    """Telemetry for the fused conv path, which bypasses ``dot_general``.

    Records the contraction the fused kernel performs — per pixel, one
    tap-axis dot: ``(B, H·W, kh·kw) @ (kh·kw, 1)`` — on the ambient meter,
    so fused and im2col runs report identical MAC/energy totals. The
    opt-in error probe samples a small leading-rows im2col slab (the
    fused kernel contracts the same zero-padded tap products, so the
    per-product error model is the same).
    """
    from repro.obs.meter import current_meter

    meter = current_meter()
    if meter is None:
        return
    b, h, w = imgs.shape
    kh, kw = kernel_arr.shape
    meter.record_contraction(s.meta, b, h * w, kh * kw, 1, site=site)
    if meter.error_probe and s.meta.mult_name != "exact":
        slab = _im2col(imgs[:1, :8], kh, kw)  # (1, ≤8, W, taps)
        meter.probe(s.meta, s.scalar, slab.reshape(1, -1, kh * kw),
                    kernel_arr.reshape(1, kh * kw, 1), site=site)


def takes_fused_path(substrate, kernel, partitioning=None) -> bool:
    """Whether :func:`conv2d_batched` can run ``kernel`` on the substrate's
    fused conv kernel: the substrate exposes ``fused_conv2d`` (the Pallas
    backends), no partitioning is requested, and the taps are concrete (a
    numpy array or Python ints; a traced kernel cannot specialize the
    fused kernel). Judged on the caller's object: ``jnp.asarray`` of the
    taps inside a jit is a tracer, the numpy array it wraps is not."""
    return (partitioning is None and hasattr(substrate, "fused_conv2d")
            and not isinstance(kernel, jax.core.Tracer))


def conv2d_batched(imgs: Array, kernel: Array,
                   substrate: "str | object" = "approx_bitexact",
                   partitioning=None, fused: "bool | None" = None,
                   site=None) -> Array:
    """Batched 'same' integer convolution via im2col + substrate contraction.

    imgs: (B, H, W) or NHWC (B, H, W, C) int32 in [-128, 127] (channels are
    convolved independently with the same kernel); kernel: (kh, kw) int32.
    substrate: spec string or ProductSubstrate; the contraction is one
    ``substrate.dot_general`` over the (B, H, W, kh·kw) tap patches —
    MXU/Pallas-friendly instead of a Python tap loop. Accumulation is exact
    int32; f(0,0) padding artifacts of the contraction are corrected inside
    the substrates. ``partitioning``: optional
    :class:`repro.nn.substrate.Partitioning` — shards the contraction
    through shard_map (bit-identical for bit-exact substrates). Returns
    int32 of imgs' shape.

    ``site`` optionally names the contraction site for per-site telemetry
    attribution (see :mod:`repro.nn.plan`); it never affects values.

    ``fused`` selects the substrate's fused conv kernel (in-kernel im2col,
    no host-side patch tensor — ``kernels/fused_conv``): ``None`` (default)
    auto-picks it where :func:`takes_fused_path` holds; ``True`` forces it
    (raising where unavailable); ``False`` forces the im2col reference
    path. Both paths are bit-identical — the fused kernel
    contracts exactly the same zero-padded tap products in the same int32
    ring.
    """
    from repro.nn import substrate as sub

    s = sub.as_substrate(substrate)
    imgs = jnp.asarray(imgs, jnp.int32)
    nhwc = imgs.ndim == 4
    if nhwc:  # fold channels into the batch: depthwise, shared kernel
        b, h, w, c = imgs.shape
        imgs = imgs.transpose(0, 3, 1, 2).reshape(b * c, h, w)
    if imgs.ndim != 3:
        raise ValueError(f"imgs must be (B,H,W) or (B,H,W,C); got {imgs.shape}")
    if fused is None:
        fused = takes_fused_path(s, kernel, partitioning)
    kernel_arr = jnp.asarray(kernel, jnp.int32)
    kh, kw = kernel_arr.shape
    if fused:
        if not hasattr(s, "fused_conv2d"):
            raise ValueError(
                f"fused=True but substrate {s.meta.spec} has no fused conv "
                "kernel (only the Pallas backends do); use fused=False")
        if partitioning is not None:
            raise ValueError(
                "fused=True is incompatible with partitioning — the fused "
                "kernel contracts K in full inside one device kernel")
        _meter_fused(s, imgs, kernel_arr, site=site)
        out = s.fused_conv2d(imgs, kernel)
    else:
        patches = _im2col(imgs, kh, kw)  # (B, H, W, kh·kw)
        spec = sub.ContractionSpec(_CONV_DIMS, partitioning=partitioning,
                                   site=site)
        out = s.dot_general(patches, kernel_arr.reshape(kh * kw, 1),
                            spec)[..., 0]
    if nhwc:
        out = out.reshape(b, c, h, w).transpose(0, 2, 3, 1)
    return out


def edge_detect(img_u8: Array, mult_name: str = "proposed") -> Array:
    """Laplacian edge map with the named multiplier; returns uint8 map.

    ``mult_name`` may carry a width suffix (``"proposed@4"``) or be a
    ``csp_*`` alias; pixels are mapped into that width's operand domain.
    Single-image reference path (tap loop); see :func:`edge_detect_batched`.
    """
    _, fn, n = mult.resolve_multiplier(mult_name)
    px = to_signed_pixels(img_u8, n)
    raw = conv2d_int(px, jnp.asarray(LAPLACIAN), fn)
    return jnp.clip(_rescale_raw(raw, n), 0, 255).astype(jnp.uint8)


def edge_detect_batched(imgs_u8: Array,
                        substrate: "str | object" = "approx_bitexact",
                        partitioning=None) -> Array:
    """Laplacian edge maps for a whole batch under one substrate.

    imgs_u8: (B, H, W) uint8. substrate: spec string (may carry a wiring +
    width suffix, e.g. ``"approx_lut:design_du2022"`` or
    ``"approx_lut:csp_axc1@4"``) or ProductSubstrate. Pixels are mapped
    into the substrate's operand width and the response rescaled back to
    the 8-bit output range. Per-image outputs are bit-identical to
    :func:`edge_detect` for every scalar-faithful substrate — including
    under a :class:`repro.nn.substrate.Partitioning` (the sharded
    contraction stays bit-identical for bit-exact substrates). Returns
    (B, H, W) uint8.
    """
    from repro.nn import substrate as sub

    s = sub.as_substrate(substrate)
    n = getattr(s.meta, "width", 8)
    px = to_signed_pixels(imgs_u8, n)
    # numpy taps stay concrete under an outer jit: Pallas substrates fuse
    raw = conv2d_batched(px, LAPLACIAN, s,
                         partitioning=partitioning, site=EDGE_SITE)
    return jnp.clip(_rescale_raw(raw, n), 0, 255).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# planned (multi-site) edge detection
# ---------------------------------------------------------------------------

#: site name of the uniform whole-kernel edge contraction
EDGE_SITE = "conv.edge"

#: the planned path's tap groups: each is a *split* of the 3×3 Laplacian —
#: (site leaf, flat tap indices into the row-major kernel). The center tap
#: (coefficient 8) dominates the response; the ring taps (all −1) are the
#: smoothing term and tolerate cheaper substrates.
_EDGE_TAP_GROUPS = (("center", (4,)), ("ring", (0, 1, 2, 3, 5, 6, 7, 8)))


def edge_tap_sites() -> tuple:
    """The planned edge workload's site names (``conv.edge.<group>``)."""
    return tuple(f"{EDGE_SITE}.{name}" for name, _ in _EDGE_TAP_GROUPS)


def edge_detect_planned(imgs_u8: Array, plan, partitioning=None) -> Array:
    """Laplacian edge maps under a per-site :class:`~repro.nn.plan.SubstratePlan`.

    The 3×3 conv splits into tap groups — ``conv.edge.center`` (the ×8
    tap) and ``conv.edge.ring`` (the eight −1 taps) — each contracted on
    the substrate the plan assigns to its site, then summed in the exact
    int32 adder. Because every substrate corrects its f(0,0) k-padding
    compensation internally, the group responses add up *bit-identically*
    to the single whole-kernel contraction whenever both groups share one
    substrate — so a uniform plan reproduces
    :func:`edge_detect_batched` exactly (asserted in tests), and the
    serving bit-identity contract (zero-pad + row-independence) carries
    over unchanged to mixed plans.

    Per-group widths ≤ 8 rescale by *left* shifts, which distribute over
    the exact adder — mixing widths above 8 would make the final
    right-shift non-distributive, so the autotuner searches widths ≤ 8.
    """
    from repro.nn import plan as plan_mod
    from repro.nn import substrate as sub

    plan = plan_mod.as_plan(plan)
    lap = LAPLACIAN.reshape(-1)
    total = None
    for name, taps in _EDGE_TAP_GROUPS:
        site = f"{EDGE_SITE}.{name}"
        s = sub.get_substrate(plan.resolve(site))
        n = getattr(s.meta, "width", 8)
        px = to_signed_pixels(imgs_u8, n)
        patches = _im2col(px, 3, 3)[..., list(taps)]
        coeffs = jnp.asarray(lap[list(taps)].reshape(len(taps), 1))
        spec = sub.ContractionSpec(_CONV_DIMS, partitioning=partitioning,
                                   site=site)
        raw = s.dot_general(patches, coeffs, spec)[..., 0]
        r = _rescale_raw(raw, n)
        total = r if total is None else total + r
    return jnp.clip(total, 0, 255).astype(jnp.uint8)


def psnr(ref: Array, test: Array, peak: float = 255.0) -> float:
    """PSNR in dB between two uint8 images (paper Fig. 9 metric).

    Computed in float32 explicitly (f64 is unavailable without
    ``jax_enable_x64`` and requesting it only triggered dtype warnings);
    uint8 differences are exactly representable in f32 and the mean over any
    realistic image size stays well inside f32 precision.
    """
    r = jnp.asarray(ref, jnp.float32)
    t = jnp.asarray(test, jnp.float32)
    mse = jnp.mean((r - t) ** 2)
    return float(jnp.where(mse == 0, jnp.inf, 10.0 * jnp.log10(peak**2 / mse)))


def conv2d_float(x: Array, kernel: Array) -> Array:
    """Float reference conv ('same', zero pad) used by NN-layer tests."""
    kh, kw = kernel.shape
    xp = jnp.pad(x, ((kh // 2, kh // 2), (kw // 2, kw // 2)))
    out = jnp.zeros_like(x)
    for di in range(kh):
        for dj in range(kw):
            out = out + kernel[di, dj] * jax.lax.dynamic_slice(xp, (di, dj), x.shape)
    return out
