"""Plain reference for the LM cells: the Minitron-8B decoder (the Nemotron
block of arXiv:2407.14679 and ``nvidia/Minitron-8B-Base``'s config), a
full-sequence forward pass in ``jax.numpy`` with no cache, no kernels and
nothing of the program imported.

Per layer, with ``LN1p(x) = LN(x)·(1+γ)+β`` and RoPE on the first half of
each head::

    h = x + W_o·Attn(RoPE½(W_q·LN1p(x)), RoPE½(W_k·LN1p(x)), W_v·LN1p(x))
    y = h + W_down·relu(W_up·LN1p(h))²

and ``logits = W_head·LN1p_f(y)``. Attention is causal grouped-query
attention (each KV head serves ``n_heads / n_kv_heads`` query heads), its
scores, softmax and value sum in float.

Precision, as the configuration file states it for the program: the
weights and the KV cache (each key after its rotation, each value) are
bfloat16; the activations and all arithmetic are float32, matmuls under
``jax.default_matmul_precision("highest")``. ``float_dtype=jnp.bfloat16``
holds the activations and does the arithmetic in bfloat16 instead: the
reading one precision below the stated one.

Projections (``linear``):

* ``"exact"``: a float32 matmul of the bfloat16 operands;
* ``"approx"``: the program's default quantization policy restated — one
  dynamic scale per contraction for the activations (the largest
  magnitude over 127), one per output column for the weights, both
  symmetric 8-bit with round-half-even — every product taken from the
  proposed multiplier's table (``bench.reference.multiplier``, built from
  the partial-product matrix; ``approx_dot`` sums its factored form on the
  MXU, equal to the table's sum) and summed exactly in int32, then
  dequantized as ``sum·(s_x·s_w)`` in float32.

The program contracts one decode step of a whole batch at once, so its
activation scale spans that step's rows. The reference is therefore given
the batch as the program ran it, ``tokens`` of shape (rows, steps), and
takes each step's scale over that step's rows; rows never mix otherwise.

Departures from the published model: the weights (and the norms' γ and β)
are random, drawn by the caller; the model is cut to the layers given.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference import multiplier

F32, BF16 = jnp.float32, jnp.bfloat16
QMAX = 127.0
EPS = 1e-8            # the guard on an all-zero operand's scale
MASKED = -1e30        # the score of a masked (future) position
VOCAB_BLOCK = 4096    # logits are made this many columns at a time


@functools.partial(jax.jit, static_argnames="axes")
def quantize(x, axes=None):
    """(int8 codes, float32 scale): symmetric 8-bit absmax over ``axes``
    (None: the whole tensor). Compiled, as the program's is: XLA turns the
    division by 127 into a multiplication by its reciprocal, and a scale
    one ulp off moves the many bfloat16 weights that sit on a rounding
    tie."""
    if axes is None:
        m = jnp.max(jnp.abs(x))
    else:
        m = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    s = jnp.maximum(m.astype(F32), EPS) / QMAX
    q = jnp.clip(jnp.round(x.astype(F32) / s), -QMAX, QMAX)
    return q.astype(jnp.int8), s


_approx_dot = jax.jit(multiplier.approx_dot)


def _column_block(k: int, n: int) -> int:
    """Columns of a weight contracted at a time: the widest multiple of 128
    that divides ``n`` within ~16 M products a block (else all ``n``)."""
    target = max(128, (1 << 24) // k // 128 * 128)
    if n <= target:
        return n
    return next((b for b in range(target, 127, -128) if n % b == 0), n)


def approx_linear(x, w, out_dtype):
    """(B, S, K) @ (K, N) on the proposed multiplier, one activation scale
    per step (over its B rows), in column blocks."""
    b, s, k = x.shape
    n = w.shape[1]
    qx, sx = quantize(x, axes=(0, 2))                    # (1, S, 1)
    qw, sw = quantize(w, axes=(0,))                      # (1, N)
    a = qx.reshape(b * s, k)
    nb = _column_block(k, n)
    blocks = qw.reshape(k, n // nb, nb).transpose(1, 0, 2)
    acc = jax.lax.map(lambda wb: _approx_dot(a, wb), blocks)
    acc = acc.transpose(1, 0, 2).reshape(b, s, n)
    return (acc.astype(F32) * (sx * sw)).astype(out_dtype)


def exact_linear(x, w, out_dtype):
    return jnp.einsum("bsk,kn->bsn", x.astype(F32), w.astype(F32)
                      ).astype(out_dtype)


LINEAR = {"exact": exact_linear, "approx": approx_linear}


def layer_norm_1p(x, gb, eps, ft):
    gamma, beta = (t.astype(ft) for t in gb)
    xf = x.astype(ft)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps) * (1.0 + gamma) + beta
    return y.astype(x.dtype)


def rope_half(x, dims, ft):
    """Rotate the first ``rotary_pct·head_dim`` dims of each head of
    (B, S, H, dh) by position, frequencies over that width."""
    dh = x.shape[-1]
    half = int(dh * dims["rotary_pct"]) // 2
    freqs = dims["rope_theta"] ** (-jnp.arange(0, half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs  # (S, half)
    cos = jnp.cos(ang)[None, :, None, :].astype(ft)
    sin = jnp.sin(ang)[None, :, None, :].astype(ft)
    x1 = x[..., :half].astype(ft)
    x2 = x[..., half:2 * half].astype(ft)
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                           x[..., 2 * half:].astype(ft)], axis=-1)
    return rot.astype(x.dtype)


def attention(q, k, v, ft):
    """Causal GQA: q (B, S, H, dh), k and v (B, S, Hkv, dh)."""
    b, s, h, dh = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, s, hkv, h // hkv, dh).astype(ft)
    sc = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k.astype(ft)) * (1.0 / math.sqrt(dh))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(causal[None, :, None, None, :], sc, MASKED)
    p = jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True))
    out = jnp.einsum("bqhgk,bkhd->bqhgd", p, v.astype(ft))
    out = out / jnp.maximum(p.sum(-1)[..., None], 1e-30)
    return out.reshape(b, s, h, dh).astype(q.dtype)


def _kv(n1, p, dims, lin, ft):
    """The layer's KV cache entries: the rotated keys and the values of
    the normed input ``n1``, in bfloat16."""
    b, s, _ = n1.shape
    hkv, dh = dims["n_kv_heads"], dims["head_dim"]
    k = lin(n1, p["wk"], n1.dtype).reshape(b, s, hkv, dh)
    v = lin(n1, p["wv"], n1.dtype).reshape(b, s, hkv, dh)
    return rope_half(k, dims, ft).astype(BF16), v.astype(BF16)


def _layer(x, p, dims, lin, ft):
    b, s, _ = x.shape
    h, dh = dims["n_heads"], dims["head_dim"]
    eps = dims["norm_eps"]
    n1 = layer_norm_1p(x, p["ln1"], eps, ft)
    q = lin(n1, p["wq"], x.dtype).reshape(b, s, h, dh)
    k, v = _kv(n1, p, dims, lin, ft)
    a = attention(rope_half(q, dims, ft), k, v, ft)
    x = x + lin(a.reshape(b, s, h * dh), p["wo"], x.dtype)
    up = lin(layer_norm_1p(x, p["ln2"], eps, ft), p["w_up"], x.dtype)
    act = jnp.square(jax.nn.relu(up.astype(ft))).astype(x.dtype)
    return x + lin(act, p["w_down"], x.dtype)


@functools.lru_cache(maxsize=None)
def _compiled(dims_items, linear, ft):
    """(layer, head, first_kv) for these dims, each compiled once. A
    compiled program's float32 arithmetic differs in its last bits from
    op-by-op execution (fusion, reassociated reductions), and such a
    difference at an 8-bit rounding boundary moves a code; the reference
    compiles as the program does."""
    dims, lin = dict(dims_items), LINEAR[linear]
    layer = jax.jit(lambda x, p: _layer(x, p, dims, lin, ft))

    @jax.jit
    def head(x, gb, w):
        y = layer_norm_1p(x, gb, dims["norm_eps"], ft).astype(F32)
        return lin(y, w, ft).astype(F32)

    @jax.jit
    def first_kv(x, p):
        n1 = layer_norm_1p(x, p["ln1"], dims["norm_eps"], ft)
        return _kv(n1, p, dims, lin, ft)

    return layer, head, first_kv


def _embed(params, tokens, ft):
    return params["emb"][jnp.asarray(tokens)].astype(ft)


def first_layer_kv(params, tokens, dims, linear="approx", float_dtype=F32):
    """The first layer's KV cache for ``tokens`` (B rows × S steps): keys
    after their rotation and values, each (B, S, n_kv_heads, head_dim) in
    bfloat16. They depend on the tokens alone (embedding, LayerNorm1p and
    two projections), not on any earlier 8-bit rounding, so a last-bit
    float difference moves at most the few codes it sits on, where the
    logits carry it through every later layer."""
    *_, first_kv = _compiled(tuple(sorted(dims.items())), linear, float_dtype)
    with jax.default_matmul_precision("highest"):
        return first_kv(_embed(params, tokens, float_dtype), params["layers"][0])


def logits(params, tokens, dims, linear="approx", float_dtype=F32,
           block=VOCAB_BLOCK):
    """Yield ``(lo, hi, logits[:, :, lo:hi])`` in float32 for ``tokens``
    (B rows × S steps, every row at positions 0..S-1), column block by
    column block of the vocabulary.

    ``params``: ``emb`` (V, d), ``head`` (d, V), ``ln_f`` (γ, β) and
    ``layers``, each with ``ln1``, ``ln2`` (γ, β) and ``wq``, ``wk``,
    ``wv``, ``wo``, ``w_up``, ``w_down`` as (in, out) matrices. ``dims``:
    ``n_heads``, ``n_kv_heads``, ``head_dim``, ``rotary_pct``,
    ``rope_theta``, ``norm_eps``.
    """
    ft = float_dtype
    layer, head, _ = _compiled(tuple(sorted(dims.items())), linear, ft)
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, ft)
        for p in params["layers"]:
            x = layer(x, p)
        w = params["head"]
        for lo in range(0, w.shape[1], block):
            hi = min(lo + block, w.shape[1])
            yield lo, hi, head(x, params["ln_f"], w[:, lo:hi])
