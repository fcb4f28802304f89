"""AdamW with decoupled weight decay.

State layout mirrors the param tree (each leaf becomes {"m": ..., "v": ...})
so the name-based sharding rules of ``launch.mesh`` apply to optimizer
state transparently.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable
    update: Callable  # (grads, state, params, lr) -> (new_params, new_state)


def adamw(b1: float = 0.9, b2: float = 0.95, eps: float = 1e-8,
          weight_decay: float = 0.01, moment_dtype=jnp.float32) -> Optimizer:
    def init(params):
        def leaf(p):
            return {"m": jnp.zeros(p.shape, moment_dtype),
                    "v": jnp.zeros(p.shape, moment_dtype)}
        return {"step": jnp.zeros((), jnp.int32),
                "mv": jax.tree_util.tree_map(leaf, params)}

    def update(grads, state, params, lr):
        step = state["step"] + 1
        c1 = 1.0 - b1 ** step.astype(jnp.float32)
        c2 = 1.0 - b2 ** step.astype(jnp.float32)

        def leaf(g, mv, p):
            g32 = g.astype(moment_dtype)
            m = b1 * mv["m"] + (1 - b1) * g32
            v = b2 * mv["v"] + (1 - b2) * jnp.square(g32)
            upd = (m / c1) / (jnp.sqrt(v / c2) + eps)
            newp = p.astype(jnp.float32) - lr * (upd.astype(jnp.float32)
                                                 + weight_decay * p.astype(jnp.float32))
            return newp.astype(p.dtype), {"m": m, "v": v}

        flat = jax.tree_util.tree_map(
            leaf, grads, state["mv"], params,
            is_leaf=lambda x: isinstance(x, dict) and set(x) == {"m", "v"})
        new_params = jax.tree_util.tree_map(
            lambda x: x[0], flat, is_leaf=lambda x: isinstance(x, tuple))
        new_mv = jax.tree_util.tree_map(
            lambda x: x[1], flat, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, {"step": step, "mv": new_mv}

    return Optimizer(init, update)
