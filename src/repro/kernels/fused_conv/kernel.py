"""Fused im2col + approximate-product conv Pallas kernel (paper Fig. 8).

TPU adaptation of the paper's FPGA row-buffer architecture, batched and
wiring-generic. Where ``nn.conv.conv2d_batched`` materializes a
(B, H, W, kh·kw) patch tensor in HBM and contracts it with a tiled matmul,
this kernel never builds the patch tensor: im2col happens *inside* the
kernel from a (block_h, W_padded) image tile in VMEM.

Halo exchange: overlapping row windows are not expressible with blocked
BlockSpec indexing, so the ops wrapper passes ``kh`` row-shifted views of
the zero-padded batch (the VMEM analogue of the paper's line buffers; the
idiom of the retired single-image ``kernels/laplacian_conv``). Inside the
kernel the kh·kw taps are static Python ints, so the products collapse
into one elementwise product map per *distinct* coefficient (the 3×3
Laplacian has two: f(x, 8) and f(x, −1)) evaluated on the whole tile,
followed by kh·kw shifted adds — exact int32 accumulation, no gathers for
closed-form product models.

Bit-identity: each output pixel accumulates exactly the products
f(x[di,dj], taps[di,dj]) over the zero-padded window — the same terms, in
the same int32 ring, as the im2col + ``dot_general`` reference path, and
no contraction-dim padding ever happens (K = kh·kw is contracted in full),
so no f(0,0) correction is needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _fused_kernel(*refs, taps, width_out, product_fn):
    view_refs, o_ref = refs[:-1], refs[-1]
    w = width_out
    acc = jnp.zeros(o_ref.shape[1:], jnp.int32)  # (bh, w)
    for di, vref in enumerate(view_refs):
        tile = vref[0].astype(jnp.int32)  # (bh, w + pad); row band di
        row = [int(c) for c in taps[di]]
        maps = {}
        for c in row:
            if c not in maps:  # one product map per distinct coefficient
                maps[c] = product_fn(tile, c)
        for dj, c in enumerate(row):
            acc = acc + jax.lax.slice_in_dim(maps[c], dj, dj + w, axis=1)
    o_ref[...] = acc[None]


def fused_conv_pallas(views, taps, product_fn, *, width_out: int,
                      block_h: int, interpret: bool = False):
    """Row-shifted views of the zero-padded batch → (B, Hb, W) conv response.

    views: tuple of ``kh`` arrays (B, Hb, Wp), view ``di`` holding rows
    ``di .. di+Hb`` of the padded batch (``Wp >= width_out + kw - 1``).
    taps: (kh, kw) nested tuples of static Python int coefficients.
    product_fn: ``fn(tile, c)`` — elementwise approximate product of an
    int32 tile with the static coefficient ``c``. Hb must be a multiple of
    ``block_h`` (the ops wrapper pads).
    """
    kh = len(taps)
    assert len(views) == kh, (len(views), kh)
    b, hb, wp = views[0].shape
    grid = (b, hb // block_h)
    view_spec = pl.BlockSpec((1, block_h, wp), lambda bb, i: (bb, i, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, taps=taps, width_out=width_out,
                          product_fn=product_fn),
        grid=grid,
        in_specs=[view_spec] * kh,
        out_specs=pl.BlockSpec((1, block_h, width_out),
                               lambda bb, i: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hb, width_out), jnp.int32),
        interpret=interpret,
        name="fused_conv",
    )(*views)
