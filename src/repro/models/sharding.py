"""Logical-axis sharding: one model code path for 1-device smoke tests and
sharded meshes.

Model code annotates tensors with *logical* axis names
(``constrain(x, "batch", "seq", "embed")``); a rules table maps logical names
to mesh axes. Outside a Mesh context (smoke tests) the annotation is a no-op.
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# logical axis -> mesh axis (or tuple of mesh axes)
DEFAULT_RULES = {
    "batch": ("pod", "data"),     # batch parallel across pods × data axis
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "expert_cap": ("pod", "data"),
    "tokens": ("pod", "data"),    # flattened batch*seq rows
    "kv_seq": None,
    "conv_w": None,
    "state": None,
    "frames": None,
}


def set_rules(rules: dict | None) -> None:
    _state.rules = rules


def get_rules() -> dict:
    return getattr(_state, "rules", None) or DEFAULT_RULES


def current_mesh() -> Optional[Mesh]:
    env = jax._src.mesh.thread_resources.env  # set by `with mesh:`
    m = env.physical_mesh
    return None if m.empty else m


def _resolve(axis_name: Optional[str], mesh: Mesh) -> Optional[object]:
    if axis_name is None:
        return None
    rule = get_rules().get(axis_name, None)
    if rule is None:
        return None
    names = rule if isinstance(rule, tuple) else (rule,)
    present = tuple(n for n in names if n in mesh.axis_names)
    if not present:
        return None
    return present if len(present) > 1 else present[0]


def spec(*logical_axes: Optional[str]) -> P:
    """PartitionSpec for logical axes under the current mesh (or empty)."""
    mesh = current_mesh()
    if mesh is None:
        return P()
    resolved = []
    used: set = set()
    for ax in logical_axes:
        r = _resolve(ax, mesh)
        # a mesh axis may appear at most once in a PartitionSpec
        if r is not None:
            rs = r if isinstance(r, tuple) else (r,)
            if any(x in used for x in rs):
                r = None
            else:
                used.update(rs)
        resolved.append(r)
    return P(*resolved)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint under a mesh; identity otherwise.

    Axes whose size does not divide the mesh-axis product are left
    unsharded (GSPMD would otherwise pad-and-shard, which is rarely wanted
    for head counts like kv=8 on a 16-way model axis).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    resolved = list(spec(*logical_axes))
    # divisibility check
    for i, r in enumerate(resolved):
        if r is None:
            continue
        names = r if isinstance(r, tuple) else (r,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if i < x.ndim and x.shape[i] % size != 0:
            resolved[i] = None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved))
    )


def named_sharding(*logical_axes: Optional[str]) -> Optional[NamedSharding]:
    mesh = current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, spec(*logical_axes))
