"""Architecture registry: config → init / loss / prefill / decode builders.

Every assigned architecture registers here; ``--arch <id>`` in the launchers
resolves through this table. Also provides ``input_specs`` —
ShapeDtypeStruct stand-ins for every model input per (arch × shape), for
lowering a cell without allocating it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models import encdec, lm, xlstm, zamba
from repro.nn import substrate as psub

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524288, 1),
}

# archs allowed to run long_500k (sub-quadratic); see DESIGN.md §5
SUBQUADRATIC = {"xlstm-125m", "zamba2-1.2b"}


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: cm.ModelConfig
    init_params: Callable
    loss_fn: Callable          # (params, batch) -> scalar
    prefill: Callable          # (params, batch) -> logits
    decode_step: Callable      # (params, state, batch) -> (logits, state)
    init_decode_state: Callable
    # the config's SubstratePlan + its default-rule ProductSubstrate,
    # both resolved once at build time
    substrate: Any = None
    plan: Any = None


def _lm_bundle(cfg: cm.ModelConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        init_params=lambda rng: lm.init_params(cfg, rng),
        loss_fn=lambda p, b: lm.loss_fn(cfg, p, b),
        prefill=lambda p, b: lm.prefill(cfg, p, b["tokens"], b.get("patch_embeds")),
        decode_step=lambda p, s, b: lm.decode_step(cfg, p, s, b["token"], b["cache_len"]),
        init_decode_state=lambda batch, max_len: lm.init_kv_caches(cfg, batch, max_len),
    )


def _xlstm_bundle(cfg: cm.ModelConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        init_params=lambda rng: xlstm.init_params(cfg, rng),
        loss_fn=lambda p, b: xlstm.loss_fn(cfg, p, b),
        prefill=lambda p, b: xlstm.prefill(cfg, p, b["tokens"]),
        decode_step=lambda p, s, b: xlstm.decode_step(cfg, p, s, b["token"], b["cache_len"]),
        init_decode_state=lambda batch, max_len: xlstm.init_decode_state(cfg, batch),
    )


def _zamba_bundle(cfg: cm.ModelConfig) -> ModelBundle:
    return ModelBundle(
        cfg=cfg,
        init_params=lambda rng: zamba.init_params(cfg, rng),
        loss_fn=lambda p, b: zamba.loss_fn(cfg, p, b),
        prefill=lambda p, b: zamba.prefill(cfg, p, b["tokens"]),
        decode_step=lambda p, s, b: zamba.decode_step(cfg, p, s, b["token"], b["cache_len"]),
        init_decode_state=lambda batch, max_len: zamba.init_decode_state(cfg, batch, max_len),
    )


def _encdec_bundle(cfg: cm.ModelConfig) -> ModelBundle:
    def init_state(batch, max_len):
        st = encdec.init_kv_caches(cfg, batch, max_len)
        st["enc_out"] = jnp.zeros((batch, cfg.n_frames, cfg.d_model), cfg.dtype)
        return st

    return ModelBundle(
        cfg=cfg,
        init_params=lambda rng: encdec.init_params(cfg, rng),
        loss_fn=lambda p, b: encdec.loss_fn(cfg, p, b),
        prefill=lambda p, b: encdec.prefill(cfg, p, b["tokens"], b["frames"]),
        decode_step=lambda p, s, b: encdec.decode_step(cfg, p, s, b["token"], b["cache_len"]),
        init_decode_state=init_state,
    )


def _with_substrate(builder: Callable) -> Callable:
    """Wrap a family builder so the config's substrate plan resolves exactly
    once at bundle build (``get_substrate`` is lru-cached, so layers
    re-resolving by spec string hit the same instances). ``bundle.substrate``
    is the plan's *default* substrate — per-site overrides resolve inside
    ``models.common.dense`` via the plan itself (``bundle.plan``)."""

    def build(cfg: cm.ModelConfig) -> ModelBundle:
        bundle = builder(cfg)
        plan = cm.substrate_plan(cfg)
        return dataclasses.replace(
            bundle, substrate=psub.get_substrate(plan.default), plan=plan)

    return build


_BUILDERS = {
    "lm": _with_substrate(_lm_bundle),
    "vlm": _with_substrate(_lm_bundle),
    "xlstm": _with_substrate(_xlstm_bundle),
    "zamba": _with_substrate(_zamba_bundle),
    "encdec": _with_substrate(_encdec_bundle),
}


def build_bundle(cfg: cm.ModelConfig) -> ModelBundle:
    """Build a bundle from an explicit config (registered or reduced)."""
    return _BUILDERS[cfg.family](cfg)

_REGISTRY: Dict[str, cm.ModelConfig] = {}


def register(cfg: cm.ModelConfig) -> cm.ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str, **overrides) -> cm.ModelConfig:
    _ensure_loaded()
    cfg = _REGISTRY[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_bundle(name: str, **overrides) -> ModelBundle:
    cfg = get_config(name, **overrides)
    return _BUILDERS[cfg.family](cfg)


def list_archs():
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    if not _REGISTRY:
        import repro.configs  # noqa: F401  (registers all archs)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------


def input_specs(cfg: cm.ModelConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStructs for every model input of this (arch, shape) cell."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def sds(shp, dt):
        return jax.ShapeDtypeStruct(shp, dt)

    if shape.kind == "train":
        batch = {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}
        if cfg.family == "vlm":
            batch["patch_embeds"] = sds((b, cfg.n_patches, cfg.d_model), cfg.dtype)
        if cfg.family == "encdec":
            batch["frames"] = sds((b, cfg.n_frames, cfg.d_model), cfg.dtype)
            batch["tokens"] = sds((b, s), i32)
            batch["labels"] = sds((b, s), i32)
        return batch
    if shape.kind == "prefill":
        batch = {"tokens": sds((b, s), i32)}
        if cfg.family == "vlm":
            batch["patch_embeds"] = sds((b, cfg.n_patches, cfg.d_model), cfg.dtype)
        if cfg.family == "encdec":
            batch["frames"] = sds((b, cfg.n_frames, cfg.d_model), cfg.dtype)
        return batch
    # decode: one new token against a cache of length seq_len
    return {"token": sds((b, 1), i32),
            "cache_len": jax.ShapeDtypeStruct((), i32)}


def decode_state_specs(bundle: ModelBundle, shape: ShapeSpec):
    """ShapeDtypeStructs of the decode state (KV caches / SSM states)."""
    return jax.eval_shape(
        lambda: bundle.init_decode_state(shape.global_batch, shape.seq_len)
    )


def param_specs(bundle: ModelBundle):
    """ShapeDtypeStructs of the parameter tree — no allocation."""
    return jax.eval_shape(lambda: bundle.init_params(jax.random.PRNGKey(0)))
