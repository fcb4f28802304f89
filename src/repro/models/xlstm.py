"""xLSTM family (arXiv:2405.04517): alternating mLSTM / sLSTM blocks.

mLSTM: matrix memory C ∈ R^{dh×dh} per head with exponential-style gating,
run as a chunked recurrence (state carried across chunks, intra-chunk
parallel quadratic form — the linear-attention identity).
sLSTM: per-head vector memory with sigmoid gates (chunk-scanned GRU-like
recurrence).

Both are sub-quadratic in sequence length with O(1) decode state — this is
the arch family that serves the ``long_500k`` shape.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import sharding as sh
from repro.nn import plan as splan

Array = jnp.ndarray
Params = Dict[str, Any]


def _split_heads(x, h):
    b, s, d = x.shape
    return x.reshape(b, s, h, d // h)


# ---------------------------------------------------------------------------
# mLSTM block
# ---------------------------------------------------------------------------


def init_mlstm(key, cfg: cm.ModelConfig) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 7)
    return {
        "ln": jnp.ones((d,), jnp.float32),
        "wq": cm.init_dense(ks[0], d, d, cfg.dtype),
        "wk": cm.init_dense(ks[1], d, d, cfg.dtype),
        "wv": cm.init_dense(ks[2], d, d, cfg.dtype),
        "wi": cm.init_dense(ks[3], d, cfg.n_heads, cfg.dtype),   # input gate
        "wf": cm.init_dense(ks[4], d, cfg.n_heads, cfg.dtype),   # forget gate
        "wo_gate": cm.init_dense(ks[5], d, d, cfg.dtype),
        "wo": cm.init_dense(ks[6], d, d, cfg.dtype),
    }


def mlstm_scan(q, k, v, i_gate, f_gate, state, chunk: int):
    """Chunked linear-attention recurrence.

    q,k,v: (B,S,H,dh); i_gate/f_gate: (B,S,H) in (0,1);
    state: (B,H,dh,dh) carried matrix memory. Returns (y, new_state).
    """
    b, s, h, dh = q.shape
    chunk = min(chunk, s)
    n = s // chunk
    assert n * chunk == s, "sequence must be divisible by chunk"

    qc = q.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    ic = i_gate.reshape(b, n, chunk, h).transpose(1, 0, 2, 3)
    fc = f_gate.reshape(b, n, chunk, h).transpose(1, 0, 2, 3)

    def body(carry, xs):
        st = carry                                  # (B,H,dh,dh)
        qq, kk, vv, ii, ff = xs
        logf = jnp.log(jnp.maximum(ff.astype(jnp.float32), 1e-6))
        lcum = jnp.cumsum(logf, axis=1)             # (B,C,H)
        # intra-chunk: M[t,u] = exp(lcum_t - lcum_u) * i_u * (q_t · k_u), u<=t
        qt = qq.astype(jnp.float32) * jnp.exp(lcum)[..., None]
        ku = kk.astype(jnp.float32) * (ii.astype(jnp.float32)
                                       * jnp.exp(-lcum))[..., None]
        scores = jnp.einsum("bthd,buhd->bhtu", qt, ku)
        mask = jnp.tril(jnp.ones((qq.shape[1], qq.shape[1]), bool))
        scores = jnp.where(mask[None, None], scores, 0.0)
        y_intra = jnp.einsum("bhtu,buhd->bthd", scores, vv.astype(jnp.float32))
        # inter-chunk: y_t += exp(lcum_t) * q_t @ state
        y_inter = jnp.einsum("bthd,bhde->bthe", qt, st)
        # state update: st' = exp(lcum_C) * st + sum_u exp(lcum_C - lcum_u) i_u k_u v_u^T
        decay_all = jnp.exp(lcum[:, -1:, :])        # (B,1,H)
        ku_tail = kk.astype(jnp.float32) * (
            ii.astype(jnp.float32) * jnp.exp(lcum[:, -1:, :] - lcum))[..., None]
        st_new = st * decay_all[:, 0, :, None, None] + jnp.einsum(
            "buhd,buhe->bhde", ku_tail, vv.astype(jnp.float32))
        return st_new, (y_intra + y_inter)

    state, ys = jax.lax.scan(jax.checkpoint(body), state, (qc, kc, vc, ic, fc))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)
    return y, state


def mlstm_block(cfg: cm.ModelConfig, p: Params, x: Array,
                state=None) -> Tuple[Array, Array]:
    b, s, d = x.shape
    h = cfg.n_heads
    xn = cm.rms_norm(x, p["ln"])
    q = _split_heads(cm.dense(cfg, xn, p["wq"]["w"], site="wq"), h) / math.sqrt(d // h)
    k = _split_heads(cm.dense(cfg, xn, p["wk"]["w"], site="wk"), h)
    v = _split_heads(cm.dense(cfg, xn, p["wv"]["w"], site="wv"), h)
    i_gate = jax.nn.sigmoid(cm.dense(cfg, xn, p["wi"]["w"], site="wi").astype(jnp.float32))
    f_gate = jax.nn.sigmoid(cm.dense(cfg, xn, p["wf"]["w"], site="wf").astype(jnp.float32) + 3.0)
    if state is None:
        state = jnp.zeros((b, h, d // h, d // h), jnp.float32)
    y, new_state = mlstm_scan(q, k, v, i_gate, f_gate, state,
                              chunk=min(cfg.attn_chunk, s))
    y = y.reshape(b, s, d).astype(x.dtype)
    gate = jax.nn.sigmoid(cm.dense(cfg, xn, p["wo_gate"]["w"], site="wo_gate").astype(jnp.float32))
    y = (y.astype(jnp.float32) * gate).astype(x.dtype)
    return x + cm.dense(cfg, y, p["wo"]["w"], site="wo").astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# sLSTM block (vector memory, chunk-scanned)
# ---------------------------------------------------------------------------


def init_slstm(key, cfg: cm.ModelConfig) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    return {
        "ln": jnp.ones((d,), jnp.float32),
        "wz": cm.init_dense(ks[0], d, d, cfg.dtype),
        "wi": cm.init_dense(ks[1], d, d, cfg.dtype),
        "wf": cm.init_dense(ks[2], d, d, cfg.dtype),
        "wo_gate": cm.init_dense(ks[3], d, d, cfg.dtype),
        "wo": cm.init_dense(ks[4], d, d, cfg.dtype),
    }


def slstm_block(cfg: cm.ModelConfig, p: Params, x: Array,
                state=None) -> Tuple[Array, Array]:
    b, s, d = x.shape
    xn = cm.rms_norm(x, p["ln"])
    z = jnp.tanh(cm.dense(cfg, xn, p["wz"]["w"], site="wz").astype(jnp.float32))
    i = jax.nn.sigmoid(cm.dense(cfg, xn, p["wi"]["w"], site="wi").astype(jnp.float32))
    f = jax.nn.sigmoid(cm.dense(cfg, xn, p["wf"]["w"], site="wf").astype(jnp.float32) + 2.0)
    if state is None:
        state = jnp.zeros((b, d), jnp.float32)

    # c_t = f_t c_{t-1} + i_t z_t  — associative scan over time (log-space-free:
    # the pair (f, i·z) composes as (f1f2, f2 b1 + b2))
    def compose(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a1 * a2, a2 * b1 + b2

    a_seq = f.transpose(1, 0, 2)                     # (S, B, d)
    b_seq = (i * z).transpose(1, 0, 2)
    # fold the carried state into the first element
    b_seq = b_seq.at[0].add(a_seq[0] * state)
    a_cum, c_seq = jax.lax.associative_scan(compose, (a_seq, b_seq))
    c = c_seq.transpose(1, 0, 2)                     # (B, S, d)
    new_state = c_seq[-1]
    o = jax.nn.sigmoid(cm.dense(cfg, xn, p["wo_gate"]["w"], site="wo_gate").astype(jnp.float32))
    y = (o * jnp.tanh(c)).astype(x.dtype)
    return x + cm.dense(cfg, y, p["wo"]["w"], site="wo").astype(x.dtype), new_state


# ---------------------------------------------------------------------------
# model assembly
# ---------------------------------------------------------------------------


def _kind(i: int) -> str:
    return "m" if i % 2 == 0 else "s"


def init_params(cfg: cm.ModelConfig, rng: Array) -> Params:
    keys = jax.random.split(rng, cfg.n_layers + 1)
    layers = []
    for i in range(cfg.n_layers):
        init = init_mlstm if _kind(i) == "m" else init_slstm
        layers.append(init(keys[i], cfg))
    return {"embed": cm.init_embed(keys[-1], cfg), "layers": layers}


def forward(cfg: cm.ModelConfig, params: Params, tokens: Array) -> Array:
    x = cm.embed(cfg, params["embed"], tokens)
    for i, layer in enumerate(params["layers"]):
        block = mlstm_block if _kind(i) == "m" else slstm_block
        kind = "mlstm" if _kind(i) == "m" else "slstm"

        def fn(xx, pp=layer, blk=block, scope=(f"layer.{i}", kind)):
            with splan.site_scope(*scope):
                return blk(cfg, pp, xx)[0]
        x = jax.checkpoint(fn)(x) if cfg.remat else fn(x)
    return x


def loss_fn(cfg: cm.ModelConfig, params: Params, batch: Dict[str, Array]) -> Array:
    x = forward(cfg, params, batch["tokens"])
    return cm.lm_loss_chunked(cfg, params["embed"], x, batch["labels"])


def init_decode_state(cfg: cm.ModelConfig, batch: int):
    states = []
    d, h = cfg.d_model, cfg.n_heads
    for i in range(cfg.n_layers):
        if i % 2 == 0:
            states.append(jnp.zeros((batch, h, d // h, d // h), jnp.float32))
        else:
            states.append(jnp.zeros((batch, d), jnp.float32))
    return states


def decode_step(cfg: cm.ModelConfig, params: Params, states, token: Array,
                cache_len: Array):
    """O(1)-state decode: one token through all recurrent blocks."""
    x = cm.embed(cfg, params["embed"], token)
    new_states = []
    for i, (layer, st) in enumerate(zip(params["layers"], states)):
        block = mlstm_block if _kind(i) == "m" else slstm_block
        kind = "mlstm" if _kind(i) == "m" else "slstm"
        with splan.site_scope(f"layer.{i}", kind):
            x, ns = block(cfg, layer, x, state=st)
        new_states.append(ns)
    logits = cm.lm_logits(cfg, params["embed"], x)
    return logits, new_states


def prefill(cfg: cm.ModelConfig, params: Params, tokens: Array) -> Array:
    x = forward(cfg, params, tokens)
    return cm.lm_logits(cfg, params["embed"], x[:, -1:, :])
