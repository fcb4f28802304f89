"""The program's Minitron (Nemotron) block against the plain reference
``bench/reference/lm.py``, on seeded random weights at a small size: the
decode steps through the KV cache, and prefill then cached decode through
``ServingEngine``, each on ``exact`` and on ``approx_pallas`` (interpret
mode), against the reference's full forward of the tokens fed.

Tolerances: the program and the reference do the same float32 arithmetic
on the same bfloat16 weights and cache (``act_dtype`` float32, the cell's
precision), and the same integer contractions; only the order of float32
sums may differ, so logits agree to ~1e-6 relative. A relative L2 error of
1e-5 and a largest error of 1e-4 (logits of RMS ~1) leave that room and
are far below a single 8-bit code moved (~1e-2). At this size no
activation sits close enough to a rounding boundary for those last-bit
differences to move a code (eight seeds read 1.0–1.7e-7); at the cell's
size some always do, and each moved code carries on through every later
layer, which is why the cell's logit limits sit above that floor and its
first-layer KV cache, which no earlier rounding feeds, is compared too.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers import lm as dlm
from bench.reference import lm as ref_lm
from bench.reference import multiplier as ref_mult
from repro.models import registry as reg
from repro.serving import ServingEngine
from repro.serving.engine import Request

SMALL = dict(n_layers=2, d_model=64, n_heads=6, n_kv_heads=2, head_dim=16,
             d_ff=128, vocab=512, act_dtype="float32")
REL_L2, MAX_ABS = 1e-5, 1e-4


def small(seed=0):
    cfg = reg.get_config("minitron-8b", **SMALL)
    bundle = reg.build_bundle(cfg)
    params = dlm.randomize_norms(bundle.init_params(jax.random.PRNGKey(seed)),
                                 jax.random.PRNGKey(seed + 1), 0.1)
    return cfg, bundle, params


def reference(cfg, params, tokens, linear):
    return np.concatenate(
        [np.asarray(r) for _, _, r in ref_lm.logits(
            dlm.reference_params(cfg, params), tokens,
            dlm.reference_dims(cfg), linear)], axis=-1)


def assert_close(got, want):
    d = got - want
    assert np.sqrt((d ** 2).sum() / (want ** 2).sum()) < REL_L2
    assert np.abs(d).max() < MAX_ABS


def test_approx_dot_sums_the_multiplier_table():
    table = ref_mult.table()
    v = np.arange(-128, 128)
    every = ref_mult.approx_dot(jnp.asarray(v[:, None], jnp.int8),
                                jnp.asarray(v[None, :], jnp.int8))
    np.testing.assert_array_equal(np.asarray(every), table)   # K = 1
    rng = np.random.default_rng(3)
    a = rng.integers(-127, 128, (5, 37))
    b = rng.integers(-127, 128, (37, 6))
    want = table[a[:, :, None] + 128, b[None, :, :] + 128].sum(1)
    got = ref_mult.approx_dot(jnp.asarray(a, jnp.int8), jnp.asarray(b, jnp.int8))
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("spec,linear", [("exact", "exact"),
                                         ("approx_pallas", "approx")])
def test_decode_steps_match_reference(spec, linear):
    """The block through ``decode_step`` and its KV cache, position by
    position, against the reference's full forward of the same tokens."""
    cfg, bundle, params = small()
    bundle = reg.build_bundle(dataclasses.replace(cfg, dot_plan=spec))
    tokens = np.random.default_rng(1).integers(1, cfg.vocab, (3, 6))
    state = bundle.init_decode_state(3, 8)
    step = jax.jit(bundle.decode_step)
    got = []
    for t in range(tokens.shape[1]):
        out, state = step(params, state, {
            "token": jnp.asarray(tokens[:, t:t + 1], jnp.int32),
            "cache_len": jnp.asarray(t, jnp.int32)})
        got.append(np.asarray(out[:, 0]))
    assert_close(np.stack(got, axis=1),
                 reference(cfg, params, tokens, linear))
    # the first layer's cache, written step by step, is the reference's
    want = ref_lm.first_layer_kv(dlm.reference_params(cfg, params), tokens,
                                 dlm.reference_dims(cfg), linear)
    for got_kv, want_kv in zip(dlm.engine_first_kv(state), want):
        np.testing.assert_array_equal(np.asarray(got_kv[:, :6], np.float32),
                                      np.asarray(want_kv, np.float32))


@pytest.mark.parametrize("spec,linear", [("exact", "exact"),
                                         ("approx_pallas", "approx")])
def test_engine_prefill_then_decode_matches_reference(spec, linear):
    cfg, bundle, params = small()
    eng = ServingEngine(bundle, params, batch_size=4, max_len=16, seed=7,
                        substrate=spec)
    fed, logits = [], []
    inner = eng._step

    def step(state, tokens, cache_len):
        out, state = inner(state, tokens, cache_len)
        fed.append(tokens.copy())
        logits.append(out)
        return out, state

    eng._step = step
    rng = np.random.default_rng(2)
    reqs = [Request(prompt=rng.integers(1, cfg.vocab, p).tolist(),
                    max_tokens=o, temperature=1.0)
            for p, o in [(1, 4), (3, 2), (5, 3), (2, 5)]]
    eng.generate(reqs)
    tokens = np.stack(fed, axis=1)
    got = np.stack(logits, axis=1)
    want = reference(cfg, params, tokens, linear)
    for i, r in enumerate(reqs):
        n = len(r.prompt) + len(r.output) - 1      # positions it was fed
        assert tokens[i, :n].tolist() == (r.prompt + r.output)[:n]
        assert_close(got[i, :n], want[i, :n])
