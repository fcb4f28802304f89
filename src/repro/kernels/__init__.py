"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel ships as kernel.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd public wrapper, padding, interpret-mode selection via
``blocking.resolve_interpret``) and ref.py (pure-jnp oracle used by the
bit-identity test sweeps).

* ``closed_form`` — the proposed design's hand-derived closed form plus
  :func:`~repro.kernels.closed_form.make_closed_form`, which generates the
  vectorized closed form for *every* CSP wiring × width 3..16 from
  ``core.multiplier``'s slot taps.
* ``approx_mul`` / ``approx_matmul`` — elementwise and tiled-matmul
  kernels over a pluggable closed-form product model (vectorized
  ``k_chunk`` k-slab walk).
* ``lut_matmul`` — matmul fallback for product models with no CSP
  structure: the scalar product is an entry of the (2^N, 2^N) product
  table, selected by int8 one-hot matmuls on the MXU (Mosaic gathers only
  within one vreg); enumerable widths 3..8.
* ``fused_conv`` — batched 'same' conv with im2col *inside* the kernel
  (row-shifted padded views, per-distinct-coefficient product maps); the
  fast path behind ``nn.conv.conv2d_batched`` for Pallas substrates.
  Absorbs the retired single-image ``laplacian_conv`` (its oracle lives on
  as ``fused_conv.ref.laplacian_conv_ref``).
"""
