"""minitron-8b's Nemotron block in the program: the registered published
values, half-width RoPE, the untied head as a plan site, and the LM
engine's spans and token counters. (Its logits against the plain reference
are in ``tests/bench/test_lm_reference.py``.)"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import common as cm
from repro.models import registry as reg
from repro.nn import plan as splan
from repro.obs.meter import ContractionMeter, telemetry_scope
from repro.obs.trace import Tracer, tracing_scope
from repro.serving import ServingEngine
from repro.serving.engine import Request

SMALL = dict(n_layers=2, d_model=64, n_heads=6, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab=512)


def test_registered_config_is_the_published_block():
    cfg = reg.get_config("minitron-8b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.dh,
            cfg.d_ff, cfg.vocab) == (32, 4096, 48, 8, 128, 16384, 256000)
    assert (cfg.mlp, cfg.norm, cfg.norm_eps) == ("relu2", "layernorm1p", 1e-5)
    assert (cfg.rotary_pct, cfg.rope_theta) == (0.5, 1e4)
    assert not cfg.tie_embeddings
    assert cfg.param_count() == pytest.approx(8.27e9, rel=1e-3)


def test_nemotron_params_have_no_gate_and_an_untied_head():
    cfg = reg.get_config("minitron-8b", **SMALL)
    params = reg.build_bundle(cfg).init_params(jax.random.PRNGKey(0))
    ffn, attn = params["unit"][0]["ffn"], params["unit"][0]["attn"]
    assert set(ffn) == {"wi", "wo", "ln", "ln_b"}
    assert set(attn) == {"wq", "wk", "wv", "wo", "ln", "ln_b"}
    assert params["embed"]["head"]["w"].shape == (64, 512)
    assert not bool(jnp.any(attn["ln"]))           # γ starts at 0: LN·(1+γ)


@pytest.mark.parametrize("pct,rotated", [(0.5, 8), (1.0, 16), (0.25, 4)])
def test_rope_rotates_only_its_share_of_each_head(pct, rotated):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 5, 3, 16)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(5, dtype=jnp.int32), (2, 5))
    y = cm.rope(x, pos, 1e4, pct)
    np.testing.assert_array_equal(y[..., rotated:], x[..., rotated:])
    assert float(jnp.abs(y[:, 1:, :, :rotated] - x[:, 1:, :, :rotated]).max()) > 1e-3
    # the rotated part is the full-width rotation of a head of that width
    np.testing.assert_allclose(y[..., :rotated],
                               cm.rope(x[..., :rotated], pos, 1e4), rtol=1e-6)


def test_untied_head_is_the_lm_head_plan_site():
    plan = splan.SubstratePlan(default="exact",
                               rules=(("lm_head", "approx_bitexact"),))
    cfg = reg.get_config("minitron-8b", **SMALL, dot_plan=plan)
    bundle = reg.build_bundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    tokens = {"tokens": jnp.asarray(np.arange(1, 9)[None], jnp.int32)}
    meter = ContractionMeter()
    with telemetry_scope(meter):
        planned = np.asarray(bundle.prefill(params, tokens))
    sites = meter.site_summary()
    assert sites["lm_head"]["specs"] == ["approx_bitexact:proposed"]
    assert all(v["specs"] == ["exact:exact"] for k, v in sites.items()
               if k != "lm_head")
    exact = reg.build_bundle(reg.get_config("minitron-8b", **SMALL))
    assert float(np.abs(planned - np.asarray(exact.prefill(params, tokens))
                        ).max()) > 0


def test_engine_spans_and_token_counters():
    cfg = reg.get_config("minitron-8b", **SMALL)
    bundle = reg.build_bundle(cfg)
    eng = ServingEngine(bundle, bundle.init_params(jax.random.PRNGKey(0)),
                        batch_size=2, max_len=16)
    reqs = [Request(prompt=[1, 2, 3], max_tokens=4, temperature=1.0),
            Request(prompt=[4], max_tokens=2)]
    tracer = Tracer()
    with tracing_scope(tracer):
        eng.generate(reqs)
    assert eng.metrics.lm_prompt_tokens == 4
    assert eng.metrics.lm_tokens == 6
    assert eng.metrics.snapshot()["lm_tokens"] == 6
    ev = tracer.events()
    steps = [e for e in ev if e["name"] == "serve.decode_step"]
    for name in ("lm.fetch", "lm.sample"):
        spans = [e for e in ev if e["name"] == name]
        assert len(spans) == len(steps) == 6        # 3 prompt + 3 more steps
        assert all(e["args"]["parent"] == "serve.decode_step" for e in spans)
    assert all(e["args"]["bytes"] == 2 * 4           # the ids, not logits
               for e in ev if e["name"] == "lm.fetch")
    eng.metrics.reset()
    assert eng.metrics.lm_tokens == eng.metrics.lm_prompt_tokens == 0


def test_act_dtype_casts_the_residual_stream_only_when_set():
    tokens = jnp.asarray([[1, 2]], jnp.int32)
    for act, want in [(None, jnp.bfloat16), ("float32", jnp.float32)]:
        cfg = reg.get_config("minitron-8b", **SMALL, act_dtype=act)
        p = cm.init_embed(jax.random.PRNGKey(0), cfg)
        assert cm.embed(cfg, p, tokens).dtype == want
