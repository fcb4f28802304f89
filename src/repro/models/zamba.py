"""Zamba2 hybrid (arXiv:2411.15242): Mamba2 backbone + shared attention block.

Mamba2 runs the SSD chunked algorithm: intra-chunk quadratic form +
inter-chunk diagonal state recurrence (state (B, H, dh, d_state) carried
by a lax.scan over chunks). The shared attention block (full transformer
block, one set of weights) is applied every ``shared_attn_every`` layers,
reusing the same parameters each time — Zamba's signature trick.

Sub-quadratic with O(1) decode state → serves ``long_500k``.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.nn import plan as splan

Array = jnp.ndarray
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Mamba2 block
# ---------------------------------------------------------------------------


def _d_inner(cfg) -> int:
    return 2 * cfg.d_model


def init_mamba(key, cfg: cm.ModelConfig) -> Params:
    d = cfg.d_model
    di = _d_inner(cfg)
    h = cfg.n_heads
    n = cfg.ssm_state
    ks = jax.random.split(key, 6)
    return {
        "ln": jnp.ones((d,), jnp.float32),
        "in_proj": cm.init_dense(ks[0], d, 2 * di + 2 * n + h, cfg.dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.conv_width, di), jnp.float32)
                   / math.sqrt(cfg.conv_width)).astype(cfg.dtype),
        "a_log": jnp.zeros((h,), jnp.float32),            # A = -exp(a_log)
        "d_skip": jnp.ones((h,), jnp.float32),
        "dt_bias": jnp.zeros((h,), jnp.float32),
        "out_proj": cm.init_dense(ks[2], di, d, cfg.dtype),
    }


def _causal_conv1d(x: Array, w: Array, state=None):
    """Depthwise causal conv. x: (B,S,C); w: (W,C); state: (B,W-1,C)."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + xp[:, i:i + x.shape[1]] * w[i][None, None, :]
    new_state = xp[:, -(width - 1):] if width > 1 else None
    return out, new_state


def mamba_scan(xh, dt, B, C, a, state, chunk: int):
    """SSD chunked recurrence.

    xh: (B,S,H,dh); dt: (B,S,H) >0; B,C: (B,S,n); a: (H,) negative;
    state: (B,H,dh,n). y_t = C_t·h_t + D-skip handled outside.
    """
    b, s, h, dh = xh.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    nc = s // chunk
    assert nc * chunk == s

    xc = xh.reshape(b, nc, chunk, h, dh).transpose(1, 0, 2, 3, 4)
    dtc = dt.reshape(b, nc, chunk, h).transpose(1, 0, 2, 3)
    Bc = B.reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)
    Cc = C.reshape(b, nc, chunk, n).transpose(1, 0, 2, 3)

    def body(st, xs):
        xx, ddt, bb, cc = xs                       # (B,C,H,dh),(B,C,H),(B,C,n)
        la = ddt * a[None, None, :]                # log decay per step (<0)
        lcum = jnp.cumsum(la, axis=1)              # (B,C,H)
        # intra-chunk: y_t = sum_{u<=t} exp(lcum_t - lcum_u) dt_u (C_t·B_u) x_u
        scores = jnp.einsum("btn,bun->btu", cc, bb)              # (B,C,C)
        decay = jnp.exp(lcum[:, :, None, :] - lcum[:, None, :, :])  # (B,t,u,H)
        mask = jnp.tril(jnp.ones((xx.shape[1], xx.shape[1]), bool))
        w = jnp.where(mask[None, :, :, None], scores[..., None] * decay, 0.0)
        y_intra = jnp.einsum("btuh,buh,buhd->bthd", w, ddt, xx)
        # inter-chunk: y_t += exp(lcum_t) C_t · st
        y_inter = jnp.einsum("bth,btn,bhdn->bthd", jnp.exp(lcum), cc, st)
        # state update
        decay_all = jnp.exp(lcum[:, -1, :])        # (B,H)
        wtail = jnp.exp(lcum[:, -1:, :] - lcum) * ddt           # (B,C,H)
        st_new = st * decay_all[:, :, None, None] + jnp.einsum(
            "buh,buhd,bun->bhdn", wtail, xx, bb)
        return st_new, y_intra + y_inter

    state, ys = jax.lax.scan(jax.checkpoint(body), state,
                             (xc.astype(jnp.float32), dtc, Bc.astype(jnp.float32),
                              Cc.astype(jnp.float32)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(b, s, h, dh)
    return y, state


def mamba_block(cfg: cm.ModelConfig, p: Params, x: Array, state=None,
                conv_state=None) -> Tuple[Array, Tuple]:
    b, s, d = x.shape
    di, h, n = _d_inner(cfg), cfg.n_heads, cfg.ssm_state
    dh = di // h
    xn = cm.rms_norm(x, p["ln"])
    proj = cm.dense(cfg, xn, p["in_proj"]["w"], site="in_proj")
    xin, z, Bm, Cm, dt_raw = jnp.split(
        proj, [di, 2 * di, 2 * di + n, 2 * di + 2 * n], axis=-1)
    xin, new_conv = _causal_conv1d(xin, p["conv_w"], conv_state)
    xin = jax.nn.silu(xin)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    a = -jnp.exp(p["a_log"])
    if state is None:
        state = jnp.zeros((b, h, dh, n), jnp.float32)
    xh = xin.reshape(b, s, h, dh)
    y, new_state = mamba_scan(xh, dt, Bm, Cm, a, state,
                              chunk=min(cfg.attn_chunk, s))
    y = y + xh.astype(jnp.float32) * p["d_skip"][None, None, :, None]
    y = (y.reshape(b, s, di) * jax.nn.silu(z.astype(jnp.float32))).astype(x.dtype)
    return (x + cm.dense(cfg, y, p["out_proj"]["w"],
                         site="out_proj").astype(x.dtype),
            (new_state, new_conv))


# ---------------------------------------------------------------------------
# model assembly
# ---------------------------------------------------------------------------


def init_params(cfg: cm.ModelConfig, rng: Array) -> Params:
    keys = jax.random.split(rng, cfg.n_layers + 3)
    layers = [init_mamba(keys[i], cfg) for i in range(cfg.n_layers)]
    shared = {"attn": cm.init_attn(keys[-3], cfg), "ffn": cm.init_ffn(keys[-2], cfg)}
    return {"embed": cm.init_embed(keys[-1], cfg), "mamba": layers, "shared": shared}


def _shared_positions(cfg) -> list:
    k = cfg.shared_attn_every
    return [i for i in range(cfg.n_layers) if k and i % k == k - 1]


def forward(cfg: cm.ModelConfig, params: Params, tokens: Array) -> Array:
    x = cm.embed(cfg, params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    shared_at = set(_shared_positions(cfg))
    for i, p in enumerate(params["mamba"]):
        def fn(xx, pp=p, scope=(f"layer.{i}", "mamba")):
            with splan.site_scope(*scope):
                return mamba_block(cfg, pp, xx)[0]
        x = jax.checkpoint(fn)(x) if cfg.remat else fn(x)
        if i in shared_at:
            def shared_fn(xx):
                with splan.site_scope("shared"):
                    y, _ = cm.attn_block(cfg, params["shared"]["attn"], xx,
                                         positions=positions)
                    return cm.ffn_block(cfg, params["shared"]["ffn"], y)
            x = jax.checkpoint(shared_fn)(x) if cfg.remat else shared_fn(x)
    return x


def loss_fn(cfg: cm.ModelConfig, params: Params, batch: Dict[str, Array]) -> Array:
    x = forward(cfg, params, batch["tokens"])
    return cm.lm_loss_chunked(cfg, params["embed"], x, batch["labels"])


def init_decode_state(cfg: cm.ModelConfig, batch: int, max_len: int):
    di, h, n = _d_inner(cfg), cfg.n_heads, cfg.ssm_state
    dh = di // h
    states = {
        "mamba": [
            (jnp.zeros((batch, h, dh, n), jnp.float32),
             jnp.zeros((batch, cfg.conv_width - 1, di), cfg.dtype))
            for _ in range(cfg.n_layers)
        ],
        "shared_kv": [
            (jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.dh), cfg.dtype),
             jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.dh), cfg.dtype))
            for _ in _shared_positions(cfg)
        ],
    }
    return states


def decode_step(cfg: cm.ModelConfig, params: Params, states, token: Array,
                cache_len: Array):
    """One decode step: O(1) mamba state + shared-attn KV lookups."""
    x = cm.embed(cfg, params["embed"], token)
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_len, (b, 1)).astype(jnp.int32)
    shared_at = _shared_positions(cfg)
    new_mamba, new_kv = [], []
    kv_i = 0
    for i, p in enumerate(params["mamba"]):
        st, conv_st = states["mamba"][i]
        with splan.site_scope(f"layer.{i}", "mamba"):
            x, (nst, ncv) = mamba_block(cfg, p, x, state=st,
                                        conv_state=conv_st)
        new_mamba.append((nst, ncv))
        if i in shared_at:
            with splan.site_scope("shared"):
                x, nkv = cm.attn_block(cfg, params["shared"]["attn"], x,
                                       positions=positions,
                                       kv_cache=states["shared_kv"][kv_i],
                                       cache_len=cache_len)
                x = cm.ffn_block(cfg, params["shared"]["ffn"], x)
            new_kv.append(nkv)
            kv_i += 1
    logits = cm.lm_logits(cfg, params["embed"], x)
    return logits, {"mamba": new_mamba, "shared_kv": new_kv}


def prefill(cfg: cm.ModelConfig, params: Params, tokens: Array) -> Array:
    x = forward(cfg, params, tokens)
    return cm.lm_logits(cfg, params["embed"], x[:, -1:, :])
