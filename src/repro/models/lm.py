"""Decoder-only LM family.

Covers: llama4-maverick (interleaved MoE top-1 + shared expert), kimi-k2
(all-MoE top-8 + shared expert), internlm2, qwen1.5 (qkv bias), gemma3
(5:1 local:global attention), minitron (the Nemotron block: LayerNorm1p,
half-width RoPE, squared-ReLU MLP, untied ``lm_head``), and the paligemma
VLM backbone (prefix patch embeddings).

Layers are grouped into a repeating *unit* (period = lcm of the MoE
interleave and the local:global pattern); params are stacked over unit
repeats and applied under ``lax.scan`` so a 61-layer 1T-param model lowers
to one unit's HLO.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import sharding as sh
from repro.nn import plan as splan

Array = jnp.ndarray
Params = Dict[str, Any]


def layer_plan(cfg: cm.ModelConfig) -> List[Dict]:
    """Per-layer block descriptors: {'moe': bool, 'window': int}."""
    plan = []
    for i in range(cfg.n_layers):
        moe = cfg.n_experts > 0 and (i % cfg.moe_interleave == cfg.moe_interleave - 1)
        window = 0
        if cfg.local_global_ratio > 0:
            # pattern: R local layers then 1 global
            window = cfg.local_window if (i % (cfg.local_global_ratio + 1)
                                          != cfg.local_global_ratio) else 0
        plan.append({"moe": moe, "window": window})
    return plan


def unit_period(cfg: cm.ModelConfig) -> int:
    p = 1
    if cfg.n_experts:
        p = max(p, cfg.moe_interleave)
    if cfg.local_global_ratio:
        p = _lcm(p, cfg.local_global_ratio + 1)
    return p


def _lcm(a: int, b: int) -> int:
    import math
    return a * b // math.gcd(a, b)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: cm.ModelConfig, desc: Dict) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"attn": cm.init_attn(k1, cfg)}
    if desc["moe"]:
        p["moe"] = cm.init_moe(k2, cfg)
    else:
        p["ffn"] = cm.init_ffn(k2, cfg)
    return p


def init_params(cfg: cm.ModelConfig, rng: Array) -> Params:
    plan = layer_plan(cfg)
    period = unit_period(cfg)
    n_units = cfg.n_layers // period
    tail = plan[n_units * period:]

    keys = jax.random.split(rng, 2 + period + len(tail))
    params: Params = {"embed": cm.init_embed(keys[0], cfg)}

    # stacked unit params: for each in-unit position u, stack over repeats
    unit = []
    for u in range(period if n_units else 0):
        desc = plan[u]

        def init_one(k, _desc=desc):
            return _init_layer(k, cfg, _desc)

        per_repeat = jax.vmap(init_one)(
            jax.random.split(keys[1 + u], n_units)
        )
        unit.append(per_repeat)
    params["unit"] = unit
    params["tail"] = [
        _init_layer(keys[1 + period + i], cfg, d) for i, d in enumerate(tail)
    ]
    if cfg.family == "vlm":
        params["patch_proj"] = cm.init_dense(keys[-1], cfg.d_model, cfg.d_model, cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _apply_layer(cfg, p, x, desc, positions, kv_cache=None, cache_len=None):
    x, new_cache = cm.attn_block(
        cfg, p["attn"], x, positions=positions, window=desc["window"],
        kv_cache=kv_cache, cache_len=cache_len,
    )
    if desc["moe"]:
        x = cm.moe_block(cfg, p["moe"], x)
    else:
        x = cm.ffn_block(cfg, p["ffn"], x)
    return x, new_cache


def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def forward(cfg: cm.ModelConfig, params: Params, tokens: Array,
            patch_embeds: Optional[Array] = None) -> Array:
    """Full-sequence forward → final hidden states (B, S, d)."""
    plan = layer_plan(cfg)
    period = unit_period(cfg)
    n_units = cfg.n_layers // period

    x = cm.embed(cfg, params["embed"], tokens)
    if cfg.family == "vlm" and patch_embeds is not None:
        pe = cm.dense(cfg, patch_embeds.astype(x.dtype),
                      params["patch_proj"]["w"], site="patch_proj")
        x = jnp.concatenate([pe, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))

    # per-repeat site names for in-unit position u: layer index r*period+u
    site_names = [[f"layer.{r * period + u}" for r in range(n_units)]
                  for u in range(period)]

    def unit_body(xc, xs):
        unit_params, repeat = xs
        for u in range(period):
            def one(xx, pp=unit_params[u], desc=plan[u], names=site_names[u]):
                with splan.scan_site_scope(repeat, names):
                    y, _ = _apply_layer(cfg, pp, xx, desc, positions)
                return y
            xc = _maybe_remat(cfg, one)(xc)
        return xc, None

    if n_units:
        x, _ = jax.lax.scan(unit_body, x,
                            (_stack_unit(params["unit"]),
                             jnp.arange(n_units)))
    for i, p in enumerate(params["tail"]):
        desc = plan[n_units * period + i]
        with splan.site_scope(f"layer.{n_units * period + i}"):
            x, _ = _apply_layer(cfg, p, x, desc, positions)
    return x


def _stack_unit(unit_list):
    """list (per in-unit position) of stacked pytrees -> scan-compatible xs."""
    return tuple(unit_list)


def loss_fn(cfg: cm.ModelConfig, params: Params, batch: Dict[str, Array]) -> Array:
    x = forward(cfg, params, batch["tokens"], batch.get("patch_embeds"))
    labels = batch["labels"]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        x = x[:, batch["patch_embeds"].shape[1]:]  # loss on text positions only
    return cm.lm_loss_chunked(cfg, params["embed"], x, labels)


# ---------------------------------------------------------------------------
# serving: prefill + single-token decode with KV caches
# ---------------------------------------------------------------------------


def init_kv_caches(cfg: cm.ModelConfig, batch: int, max_len: int) -> List:
    """Stacked per-unit-position caches + tail caches."""
    period = unit_period(cfg)
    n_units = cfg.n_layers // period
    plan = layer_plan(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.dh

    def mk(shape):
        z = jnp.zeros(shape, cfg.dtype)
        return z

    unit_caches = [
        (mk((n_units, batch, max_len, hkv, dh)), mk((n_units, batch, max_len, hkv, dh)))
        for _ in range(period)
    ]
    tail_caches = [
        (mk((batch, max_len, hkv, dh)), mk((batch, max_len, hkv, dh)))
        for _ in plan[n_units * period:]
    ]
    return {"unit": unit_caches, "tail": tail_caches}


def decode_step(cfg: cm.ModelConfig, params: Params, caches, token: Array,
                cache_len: Array) -> Tuple[Array, Any]:
    """One decode step: token (B, 1) int32 → logits (B, 1, V), new caches."""
    plan = layer_plan(cfg)
    period = unit_period(cfg)
    n_units = cfg.n_layers // period

    x = cm.embed(cfg, params["embed"], token)
    b = x.shape[0]
    positions = jnp.broadcast_to(cache_len, (b, 1)).astype(jnp.int32)

    site_names = [[f"layer.{r * period + u}" for r in range(n_units)]
                  for u in range(period)]

    new_unit_caches = []
    if n_units:
        def unit_body(xc, xs):
            unit_params, unit_cache, repeat = xs
            new_caches_u = []
            for u in range(period):
                with splan.scan_site_scope(repeat, site_names[u]):
                    y, nc = _apply_layer(cfg, unit_params[u], xc, plan[u],
                                         positions, kv_cache=unit_cache[u],
                                         cache_len=cache_len)
                new_caches_u.append(nc)
                xc = y
            return xc, tuple(new_caches_u)

        x, new_unit = jax.lax.scan(
            unit_body, x, (_stack_unit(params["unit"]),
                           tuple(caches["unit"]), jnp.arange(n_units))
        )
        new_unit_caches = list(new_unit)
    new_tail = []
    for i, p in enumerate(params["tail"]):
        desc = plan[n_units * period + i]
        with splan.site_scope(f"layer.{n_units * period + i}"):
            x, nc = _apply_layer(cfg, p, x, desc, positions,
                                 kv_cache=caches["tail"][i],
                                 cache_len=cache_len)
        new_tail.append(nc)
    logits = cm.lm_logits(cfg, params["embed"], x)
    return logits, {"unit": new_unit_caches, "tail": new_tail}


def prefill(cfg: cm.ModelConfig, params: Params, tokens: Array,
            patch_embeds: Optional[Array] = None) -> Array:
    """Prefill forward: returns last-position logits (caches implicit — a
    serving engine would also emit KV)."""
    x = forward(cfg, params, tokens, patch_embeds)
    return cm.lm_logits(cfg, params["embed"], x[:, -1:, :])
