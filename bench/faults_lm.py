"""Faults planted under the LM cells' timed path (and one in their
reference), to show that ``correct`` catches them; each is a context
manager, as in ``bench/faults.py``. ``bench/calibrate_lm.py`` reads them
on the chip and ``tests/bench`` on the CPU."""
from __future__ import annotations

import contextlib

from bench.faults import _patched

ALTERED_ID = 1         # the vocabulary entry the altered logit is at
ALTERATION = 4.0       # added to it, in RMS logits of its row


def altered_logit():
    """One logit of slot 0 altered by ``ALTERATION`` times its row's RMS
    logit at every step, as the step's logits reach the host."""
    import numpy as np

    from repro.serving.engine import ServingEngine

    def make(inner):
        def step(self, state, tokens, cache_len):
            logits, state = inner(self, state, tokens, cache_len)
            logits = logits.copy()
            logits[0, ALTERED_ID] += ALTERATION * np.sqrt(
                np.mean(np.square(logits[0])))
            return logits, state
        return step

    return _patched(ServingEngine, "_step", make)


def stale_cache():
    """Slot 0's KV cache one position off: after each step its newest key
    and value move one position back, over the previous token's, and the
    position they left is zeroed."""
    import jax
    import jax.numpy as jnp

    from repro.serving.engine import ServingEngine

    @jax.jit
    def shift(state, n):
        def one(c):                 # (..., slots, positions, kv heads, dh)
            s0 = c[..., 0, :, :, :]
            new = jax.lax.dynamic_slice_in_dim(s0, n, 1, axis=-3)
            s0 = jax.lax.dynamic_update_slice_in_dim(s0, new, n - 1, axis=-3)
            s0 = jax.lax.dynamic_update_slice_in_dim(
                s0, jnp.zeros_like(new), n, axis=-3)
            return c.at[..., 0, :, :, :].set(s0)
        return jax.tree_util.tree_map(one, state)

    def make(inner):
        def step(self, state, tokens, cache_len):
            logits, state = inner(self, state, tokens, cache_len)
            if cache_len >= 1:
                state = shift(state, cache_len)
            return logits, state
        return step

    return _patched(ServingEngine, "_step", make)


def low_precision_activations():
    """The program's activations in bfloat16 (the model's default), below
    the float32 the configuration states."""
    import dataclasses

    from bench.drivers import lm as dlm

    def make(inner):
        def model_config(config):
            return dataclasses.replace(inner(config), act_dtype=None)
        return model_config

    return _patched(dlm, "model_config", make)


@contextlib.contextmanager
def low_precision_reference():
    """The reference's float arithmetic in bfloat16: the reading one
    precision below the configuration's."""
    import jax.numpy as jnp

    from bench.reference import lm as ref_lm

    def make(inner):
        def lowered(*args, **kw):
            return inner(*args, **dict(kw, float_dtype=jnp.bfloat16))
        return lowered

    with _patched(ref_lm, "logits", make), \
            _patched(ref_lm, "first_layer_kv", make):
        yield


FAULTS = {f.__name__: f for f in (altered_logit, stale_cache,
                                   low_precision_activations,
                                   low_precision_reference)}
