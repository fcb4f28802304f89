"""Shared model config + transformer building blocks.

Pure-functional layers (params = nested dicts of jnp arrays) designed to
lower efficiently at 1T-parameter scale:

* layers applied under ``lax.scan`` over stacked params (compact HLO);
* attention uses online-softmax over KV chunks (no S×S score tensor — a
  32k-token prefill would otherwise materialize petabytes);
* LM loss is chunked over the sequence (big-vocab logits never fully
  materialize);
* MoE uses capacity-based sort-free dispatch (bincount ranks + scatter),
  giving the true T·k/E expert FLOP profile instead of dense all-experts;
* every matmul routes through ``dense()`` which resolves ``cfg.dot_mode``
  through the :mod:`repro.nn.substrate` ProductSubstrate registry — the
  paper's approximate multiplier (and its Pallas TPU kernel,
  ``approx_pallas``) is a first-class execution mode of the whole model zoo.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import sharding as sh
from repro.nn import plan as splan
from repro.nn import substrate as psub

Array = jnp.ndarray
Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                    # lm | encdec | vlm | xlstm | zamba
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0              # 0 -> d_model // n_heads
    # MoE
    n_experts: int = 0
    top_k: int = 1
    moe_interleave: int = 1        # MoE every k-th layer
    shared_expert: bool = False
    capacity_factor: float = 1.25
    # attention
    qkv_bias: bool = False
    local_window: int = 0          # sliding-window size for local layers
    local_global_ratio: int = 0    # e.g. 5 -> 5 local : 1 global
    rope_theta: float = 1e4
    # SSM / recurrent
    ssm_state: int = 0
    conv_width: int = 4
    shared_attn_every: int = 0     # zamba: shared attention block period
    # modality frontend stubs
    n_frames: int = 0              # whisper encoder frames (post-conv stub)
    n_patches: int = 0             # paligemma image patches
    # encoder (enc-dec only)
    n_encoder_layers: int = 0
    # execution
    dtype: Any = jnp.bfloat16
    dot_mode: str = "exact"        # DEPRECATED single substrate spec
                                   # "backend[:mult_name]"; kept as the
                                   # uniform-plan shim — prefer dot_plan
    dot_plan: Any = None           # site-addressed substrate assignment:
                                   # a repro.nn.plan.SubstratePlan (or a
                                   # spec string / plan dict, normalized by
                                   # substrate_plan()); None → dot_mode
    remat: bool = True
    attn_chunk: int = 512
    loss_chunk: int = 512
    cost_unroll: bool = False   # unroll inner (seq-chunk) scans so XLA
                                # cost_analysis counts every iteration —
                                # used by the roofline cost lowerings only

    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def d_ff_expert(self) -> int:
        return self.d_ff

    def param_count(self) -> int:
        """Total parameter count (used for 6·N·D model FLOPs)."""
        d, v = self.d_model, self.vocab
        attn = d * self.n_heads * self.dh + 2 * d * self.n_kv_heads * self.dh \
            + self.n_heads * self.dh * d
        dense_ffn = 3 * d * self.d_ff
        emb = v * d
        if self.family == "xlstm":
            per_layer = 8 * d * d // 2  # m/sLSTM projections (approx.)
            return self.n_layers * per_layer + 2 * emb
        if self.family == "zamba":
            d_in = 2 * d
            mamba = d * (2 * d_in + 2 * self.ssm_state + 32) + d_in * d
            n_attn = self.n_layers // max(1, self.shared_attn_every)
            return self.n_layers * mamba + (attn + dense_ffn) + emb
        n_moe = self.n_layers // self.moe_interleave if self.n_experts else 0
        n_dense = self.n_layers - n_moe
        moe_ffn = n_moe * (self.n_experts * 3 * d * self.d_ff_expert
                           + d * self.n_experts
                           + (3 * d * self.d_ff_expert if self.shared_expert else 0))
        total = self.n_layers * attn + n_dense * dense_ffn + moe_ffn + emb
        if self.family == "encdec":
            total += self.n_encoder_layers * (attn + dense_ffn + attn)  # + cross-attn
        return total

    def active_param_count(self) -> int:
        """Activated params per token (MoE: top_k + shared instead of all)."""
        if not self.n_experts:
            return self.param_count()
        d = self.d_model
        n_moe = self.n_layers // self.moe_interleave
        all_experts = n_moe * self.n_experts * 3 * d * self.d_ff_expert
        active = n_moe * (self.top_k + (1 if self.shared_expert else 0)) \
            * 3 * d * self.d_ff_expert
        return self.param_count() - all_experts + active


# ---------------------------------------------------------------------------
# Primitive layers
# ---------------------------------------------------------------------------


#: dense()'s quantization boundary: the historical `dot` policy (per-tensor
#: dynamic activation scale, per-output-channel weight scales).
_DENSE_QUANT = psub.QuantPolicy()


def substrate_plan(cfg: ModelConfig) -> "splan.SubstratePlan":
    """The plan governing this trace: ambient override, else the config's.

    An active :func:`repro.nn.plan.plan_override_scope` wins outright — it
    is how a layer above an already-built model function (the train loop
    resuming under a checkpoint's recorded plan) changes the numerics of
    the whole trace. Otherwise ``cfg.dot_plan`` wins when set (a plan, spec
    string, or plan dict — normalized through
    :func:`repro.nn.plan.as_plan`); otherwise the legacy ``cfg.dot_mode``
    spec auto-wraps into a uniform single-rule plan. The legacy path emits
    a DeprecationWarning for non-default specs — set
    ``dot_plan=SubstratePlan.uniform(spec)`` (or just ``dot_plan=spec``)
    instead.
    """
    override = splan.current_plan_override()
    if override is not None:
        return override
    if cfg.dot_plan is not None:
        return splan.as_plan(cfg.dot_plan)
    if cfg.dot_mode != "exact":
        warnings.warn(
            "cfg.dot_mode is deprecated; set cfg.dot_plan to a "
            "repro.nn.plan.SubstratePlan (a spec string still means a "
            "uniform plan)", DeprecationWarning, stacklevel=3)
    return splan.SubstratePlan.uniform(cfg.dot_mode)


def dense(cfg: ModelConfig, x: Array, w: Array, b: Optional[Array] = None,
          *, site: Optional[str] = None) -> Array:
    """Matmul under the configured product substrate (the paper's technique).

    The substrate is chosen by the config's :func:`substrate_plan` at the
    ambient contraction site (``site`` is the leaf segment under the
    enclosing :func:`repro.nn.plan.site_scope` stack — e.g. ``"wq"`` under
    ``layer.3.attn`` resolves at ``layer.3.attn.wq``). Resolution is
    lru-cached per (plan, site), so per-call overhead is negligible.

    Under a :func:`repro.nn.plan.scan_site_scope` (stacked layers traced
    once under ``lax.scan``), the per-repeat assignments are resolved at
    trace time: when every repeat agrees — the common case — the call
    stays a single static ``dot_general``; otherwise the distinct
    substrates become ``jax.lax.switch`` branches selected by the carried
    layer index, so mixed per-layer plans survive stacked params.

    The contraction runs through ``dot_general`` with the default
    quantization policy; when a
    :func:`repro.nn.substrate.partitioning_scope` is active (the launch
    layer's ``--dot-partition`` mesh path), the contraction lowers through
    shard_map instead of relying on GSPMD to shard the scalar-emulation HLO.
    """
    plan = substrate_plan(cfg)
    part = psub.current_partitioning()
    override = psub.current_dot_override()
    d = splan.dispatch(plan, site)
    if d.index is None:
        spec_str, label = d.groups[0]
        cspec = psub.ContractionSpec.matmul(
            quant=_DENSE_QUANT, partitioning=part, site=label)
        if override is not None:
            out = override(spec_str, x, w, cspec)
        else:
            out = psub.get_substrate(spec_str).dot_general(x, w, cspec)
    else:
        branches = []
        for spec_str, label in d.groups:
            cspec = psub.ContractionSpec.matmul(
                quant=_DENSE_QUANT, partitioning=part, site=label)

            if override is not None:
                def branch(xx, ww, _spec=spec_str, _cs=cspec, _ov=override):
                    return _ov(_spec, xx, ww, _cs)
            else:
                def branch(xx, ww, _s=psub.get_substrate(spec_str), _cs=cspec):
                    return _s.dot_general(xx, ww, _cs)

            branches.append(branch)
        sel = jnp.asarray(np.asarray(d.branch_of, np.int32))[d.index]
        out = jax.lax.switch(sel, branches, x, w)
    if b is not None:
        out = out + b.astype(out.dtype)
    return out


def rms_norm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) / math.sqrt(d_in)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope(x: Array, positions: Array, theta: float) -> Array:
    """x: (B, S, H, dh); positions: (B, S) int32."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if dh % 2:
        rot = jnp.concatenate([rot, x[..., -1:]], axis=-1)
    return rot.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (GQA, online softmax over KV chunks, causal/local windows)
# ---------------------------------------------------------------------------


def attention_chunked(q: Array, k: Array, v: Array, *, q_offset: Array,
                      causal: bool = True, window: int = 0,
                      chunk: int = 512, unroll: bool = False) -> Array:
    """Online-softmax attention.

    q: (B, Sq, H, dh); k, v: (B, Skv, Hkv, dh); q_offset: scalar — the
    absolute position of q[0] (Sq == Skv and offset 0 during training;
    decode passes Sq=1, offset=cache_len). window > 0 = sliding-window
    (local) attention. Never materializes an (Sq, Skv) score tensor larger
    than (Sq, chunk).
    """
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    group = h // hkv
    qg = q.reshape(b, sq, hkv, group, dh)
    scale = 1.0 / math.sqrt(dh)
    chunk = min(chunk, skv)
    n_chunks = skv // chunk
    rem = skv - n_chunks * chunk

    q_pos = q_offset + jnp.arange(sq)

    def score_block(k_blk, v_blk, kv_start):
        # k_blk: (B, C, Hkv, dh) -> scores (B, Sq, Hkv, G, C)
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qg.astype(jnp.float32),
                       k_blk.astype(jnp.float32)) * scale
        kv_pos = kv_start + jnp.arange(k_blk.shape[1])
        mask = jnp.ones((sq, k_blk.shape[1]), bool)
        if causal:
            mask &= q_pos[:, None] >= kv_pos[None, :]
        if window > 0:
            mask &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        return s, v_blk

    def combine(carry, blk):
        m_prev, l_prev, acc = carry
        s, v_blk = blk
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_prev * jnp.exp(m_prev - m_new) + p.sum(-1)
        acc = acc * jnp.exp(m_prev - m_new)[..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p, v_blk.astype(jnp.float32))
        return (m_new, l_new, acc)

    m0 = jnp.full((b, sq, hkv, group), -1e30, jnp.float32)
    l0 = jnp.zeros((b, sq, hkv, group), jnp.float32)
    a0 = jnp.zeros((b, sq, hkv, group, dh), jnp.float32)
    carry = (m0, l0, a0)

    if n_chunks:
        kc = k[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, hkv, dh)
        vc = v[:, :n_chunks * chunk].reshape(b, n_chunks, chunk, hkv, dh)

        def body(c, xs):
            k_blk, v_blk, idx = xs
            return combine(c, score_block(k_blk, v_blk, idx * chunk)), None

        # nested remat: recompute per-chunk scores in the backward pass
        # instead of saving (Sq × chunk) residuals per step
        body = jax.checkpoint(body)
        carry, _ = jax.lax.scan(
            body, carry,
            (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4),
             jnp.arange(n_chunks)),
            unroll=n_chunks if unroll else 1,
        )
    if rem:
        carry = combine(carry, score_block(k[:, n_chunks * chunk:],
                                           v[:, n_chunks * chunk:],
                                           n_chunks * chunk))
    _, l, acc = carry
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, dh).astype(q.dtype)


def init_attn(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 4)
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh
    return {
        "wq": init_dense(ks[0], d, h * dh, cfg.dtype, cfg.qkv_bias),
        "wk": init_dense(ks[1], d, hkv * dh, cfg.dtype, cfg.qkv_bias),
        "wv": init_dense(ks[2], d, hkv * dh, cfg.dtype, cfg.qkv_bias),
        "wo": init_dense(ks[3], h * dh, d, cfg.dtype),
        "ln": jnp.ones((d,), jnp.float32),
    }


def attn_block(cfg: ModelConfig, p: Params, x: Array, *, positions: Array,
               window: int = 0, kv_cache: Optional[Tuple[Array, Array]] = None,
               cache_len: Optional[Array] = None, cross_kv=None,
               causal: bool = True,
               ) -> Tuple[Array, Optional[Tuple[Array, Array]]]:
    """Pre-norm GQA attention block. Returns (residual output, new kv).

    kv_cache: (K, V) of shape (B, S_max, Hkv, dh) for decode; cache_len is
    the current length (new token written at that index).
    cross_kv: precomputed (K, V) for encoder-decoder cross attention.
    """
    b, s, d = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    xn = rms_norm(x, p["ln"])
    with splan.site_scope("attn"):
        q = dense(cfg, xn, p["wq"]["w"], p["wq"].get("b"),
                  site="wq").reshape(b, s, h, dh)
        if cross_kv is None:
            k = dense(cfg, xn, p["wk"]["w"], p["wk"].get("b"),
                      site="wk").reshape(b, s, hkv, dh)
            v = dense(cfg, xn, p["wv"]["w"], p["wv"].get("b"),
                      site="wv").reshape(b, s, hkv, dh)
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        else:
            k, v = cross_kv

    q = sh.constrain(q, "batch", "seq", "heads", "head_dim")

    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k.astype(ck.dtype), cache_len, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v.astype(cv.dtype), cache_len, axis=1)
        new_cache = (ck, cv)
        k, v = ck, cv
        q_offset = cache_len
    else:
        q_offset = jnp.array(0, jnp.int32) if cross_kv is None else None
        causal = causal and cross_kv is None

    out = attention_chunked(
        q, k, v,
        q_offset=(q_offset if q_offset is not None else jnp.array(0, jnp.int32)),
        causal=causal, window=window, chunk=cfg.attn_chunk,
        unroll=cfg.cost_unroll,
    )
    with splan.site_scope("attn"):
        out = dense(cfg, out.reshape(b, s, h * dh), p["wo"]["w"], site="wo")
    return x + out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Dense (SwiGLU) FFN
# ---------------------------------------------------------------------------


def init_ffn(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, d_ff or cfg.d_ff
    return {
        "wi": init_dense(ks[0], d, f, cfg.dtype),
        "wg": init_dense(ks[1], d, f, cfg.dtype),
        "wo": init_dense(ks[2], f, d, cfg.dtype),
        "ln": jnp.ones((d,), jnp.float32),
    }


def ffn_block(cfg: ModelConfig, p: Params, x: Array) -> Array:
    xn = rms_norm(x, p["ln"])
    with splan.site_scope("ffn"):
        hidden = (jax.nn.silu(dense(cfg, xn, p["wg"]["w"], site="wg"))
                  * dense(cfg, xn, p["wi"]["w"], site="wi"))
        hidden = sh.constrain(hidden, "batch", "seq", "mlp")
        return x + dense(cfg, hidden, p["wo"]["w"], site="wo").astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture of Experts (capacity dispatch; expert-parallel over "model" axis)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    d, f, e = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    std = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(ks[0], (d, e), jnp.float32) * std),
        "wi": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * std).astype(cfg.dtype),
        "wg": (jax.random.normal(ks[2], (e, d, f), jnp.float32) * std).astype(cfg.dtype),
        "wo": (jax.random.normal(ks[3], (e, f, d), jnp.float32) / math.sqrt(f)).astype(cfg.dtype),
        "ln": jnp.ones((d,), jnp.float32),
    }
    if cfg.shared_expert:
        p["shared"] = init_ffn(ks[4], cfg, cfg.d_ff_expert)
    return p


def moe_block(cfg: ModelConfig, p: Params, x: Array) -> Array:
    """Top-k capacity-based MoE (token-dropping on overflow).

    Under a mesh with a "model" axis, dispatch runs EXPERT-PARALLEL via
    shard_map: every data shard routes its own tokens locally (local
    scatter into an (E, C_local, d) buffer), an all-to-all over the model
    axis moves token slots to their expert owners, experts run as batched
    matmuls on the local expert shard, and a reverse all-to-all brings
    results home — the production EP pattern with *explicit* collectives
    (GSPMD replicates computed-index scatters otherwise; measured: 748 GB →
    few-GB temp on kimi-k2). Without a mesh (smoke tests / tiny batches)
    the same dispatch runs as plain local ops.
    """
    mesh = sh.current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        t = x.shape[0] * x.shape[1]
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        n_shards = mesh.shape["model"]
        for a in dp:
            n_shards *= mesh.shape[a]
        if (t % n_shards == 0 and cfg.n_experts % mesh.shape["model"] == 0):
            return _moe_block_ep(cfg, p, x, mesh, dp)
    return _moe_block_local(cfg, p, x)


def _dispatch_local(cfg: ModelConfig, xn: Array, router: Array):
    """Route tokens: returns (buf (E, C, d), combine info). Pure-local ops.

    Ranking within each expert is SORT-based: O(T·logT) compares instead of
    the textbook O(T·E) one-hot cumsum — at kimi-k2 scale (T·k = 0.5 M rows
    per shard, E = 384) the cumsum's (T·k, E) int tensor dominated the whole
    step's memory traffic (measured: ~40 % of t_memory; see EXPERIMENTS.md
    §Perf iteration 1).
    """
    t, d = xn.shape
    e, k = cfg.n_experts, cfg.top_k
    gates = jax.nn.softmax(jnp.dot(xn.astype(jnp.float32), router), axis=-1)
    topw, topi = jax.lax.top_k(gates, k)                       # (t, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    cap = int(max(1, math.ceil(t * k * cfg.capacity_factor / e)))
    flat_e = topi.reshape(-1)                                  # (t*k,)
    order = jnp.argsort(flat_e, stable=True)                   # token-order ties
    sorted_e = flat_e[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(e))          # group starts
    rank_sorted = jnp.arange(t * k) - start[sorted_e]
    my_rank = jnp.zeros((t * k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))
    keep = my_rank < cap
    slot = jnp.where(keep, flat_e * cap + my_rank, e * cap)
    tok_idx = jnp.repeat(jnp.arange(t), k)
    buf = jnp.zeros((e * cap + 1, d), xn.dtype).at[slot].set(xn[tok_idx])
    return buf[:e * cap].reshape(e, cap, d), (slot, topw, keep, cap)


def _combine_local(out: Array, info, t: int):
    """Inverse of _dispatch_local: weighted gather back to token order."""
    slot, topw, keep, cap = info
    e = out.shape[0]
    d = out.shape[-1]
    out_flat = jnp.concatenate([out.reshape(e * cap, d),
                                jnp.zeros((1, d), out.dtype)], axis=0)
    gathered = out_flat[slot]                                  # (t*k, d)
    w = (topw.reshape(-1) * keep).astype(gathered.dtype)
    k = topw.shape[1]
    return (gathered * w[:, None]).reshape(t, k, d).sum(axis=1)


def _expert_ffn(p: Params, buf: Array) -> Array:
    hid = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(buf.dtype)))
    hid = hid * jnp.einsum("ecd,edf->ecf", buf, p["wi"].astype(buf.dtype))
    return jnp.einsum("ecf,efd->ecd", hid, p["wo"].astype(hid.dtype))


def _moe_block_local(cfg: ModelConfig, p: Params, x: Array) -> Array:
    b, s, d = x.shape
    t = b * s
    xn = rms_norm(x, p["ln"]).reshape(t, d)
    buf, info = _dispatch_local(cfg, xn, p["router"])
    out = _expert_ffn(p, buf)
    y = _combine_local(out, info, t)
    if cfg.shared_expert:
        with splan.site_scope("moe", "shared"):
            y = y + (ffn_block(cfg, p["shared"], xn.reshape(b, s, d))
                     - xn.reshape(b, s, d)).reshape(t, d)
    return x + y.reshape(b, s, d).astype(x.dtype)


def _moe_block_ep(cfg: ModelConfig, p: Params, x: Array, mesh, dp) -> Array:
    """Expert-parallel MoE: shard_map(local dispatch → a2a → FFN → a2a).

    Every device must route a DISTINCT token slice (replicating tokens over
    "model" computes every dispatch M× redundantly — measured as an 18×
    useful-flops gap, §Perf iteration 2), but exposing a dp×model token
    sharding at the shard_map boundary makes GSPMD fall back to full
    rematerialization when resharding the remat residuals (measured:
    2.8 TiB/layer of all-gathers, §Perf iteration 3). So the boundary stays
    dp-sharded and each model shard SLICES its 1/M share inside the body —
    the reshard becomes an explicit slice + all-gather pair that transposes
    cleanly in the backward pass.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    t = b * s
    xn = rms_norm(x, p["ln"]).reshape(t, d)
    m_size = mesh.shape["model"]

    def body(xn_l, router, wi_l, wg_l, wo_l):
        # xn_l: (t_dp, d) — replicated over "model"; take this shard's share
        t_mm = xn_l.shape[0] // m_size
        m_idx = jax.lax.axis_index("model")
        xn_mine = jax.lax.dynamic_slice_in_dim(xn_l, m_idx * t_mm, t_mm, 0)
        buf, info = _dispatch_local(cfg, xn_mine, router)       # (E, C_l, d)
        # all-to-all: split expert dim across "model", gather capacity dim
        buf = jax.lax.all_to_all(buf, "model", split_axis=0, concat_axis=1,
                                 tiled=True)                    # (E_l, C_l*M, d)
        out = _expert_ffn({"wi": wi_l, "wg": wg_l, "wo": wo_l}, buf)
        out = jax.lax.all_to_all(out, "model", split_axis=1, concat_axis=0,
                                 tiled=True)                    # (E, C_l, d)
        y_mine = _combine_local(out, info, t_mm)                # (t_mm, d)
        return jax.lax.all_gather(y_mine, "model", axis=0, tiled=True)

    dp_spec = dp if len(dp) > 1 else dp[0]
    y = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_spec, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=P(dp_spec, None),
        check_vma=False,
    )(xn, p["router"], p["wi"], p["wg"], p["wo"])

    if cfg.shared_expert:
        with splan.site_scope("moe", "shared"):
            y = y + (ffn_block(cfg, p["shared"], xn.reshape(b, s, d))
                     - xn.reshape(b, s, d)).reshape(t, d)
    return x + y.reshape(b, s, d).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / LM head / chunked loss
# ---------------------------------------------------------------------------


def init_embed(key, cfg: ModelConfig) -> Params:
    emb = jax.random.normal(key, (cfg.vocab, cfg.d_model), jnp.float32)
    return {"emb": (emb / math.sqrt(cfg.d_model)).astype(cfg.dtype),
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32)}


def embed(cfg: ModelConfig, p: Params, tokens: Array) -> Array:
    e = sh.constrain(p["emb"], "vocab", "embed")
    x = e[tokens]
    return sh.constrain(x, "batch", "seq", "embed")


def lm_loss_chunked(cfg: ModelConfig, p: Params, x: Array, labels: Array) -> Array:
    """Streaming softmax-xent: never materializes (B, S, V) at once."""
    b, s, d = x.shape
    x = rms_norm(x, p["ln_f"])
    chunk = min(cfg.loss_chunk, s)
    n = s // chunk
    emb_t = p["emb"].astype(jnp.float32).T  # (d, V)

    def body(acc, idx):
        xs = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, axis=1)
        logits = jnp.einsum("bsd,dv->bsv", xs.astype(jnp.float32), emb_t)
        logits = sh.constrain(logits, "batch", "seq", "vocab")
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return acc + (logz - gold).sum(), None

    total, _ = jax.lax.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                            jnp.arange(n),
                            unroll=n if cfg.cost_unroll else 1)
    rem = s - n * chunk
    if rem:
        logits = jnp.einsum("bsd,dv->bsv", x[:, n * chunk:].astype(jnp.float32), emb_t)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, n * chunk:][..., None], -1)[..., 0]
        total = total + (logz - gold).sum()
    return total / (b * s)


def lm_logits(cfg: ModelConfig, p: Params, x: Array) -> Array:
    x = rms_norm(x, p["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                        p["emb"].astype(jnp.float32))
    return sh.constrain(logits, "batch", "seq", "vocab")
