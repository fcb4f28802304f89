"""Production mesh + parameter sharding rules.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state). Single pod: 16×16 = 256 chips (TPU v5e pod),
axes ("data", "model"). Multi-pod: 2×16×16 = 512 chips, axes
("pod", "data", "model") — the "pod" axis carries pure data parallelism
across the DCN/ICI boundary.

Parameter sharding is FSDP+TP hybrid, assigned by leaf-path name rules:
the contraction/feature dims of the big weights shard over ("pod","data")
(FSDP — gathered per layer under the scan) and the head/mlp/expert output
dims over "model" (TP/EP). Dims that don't divide evenly stay unsharded.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (sharding-propagated).

    Since JAX 0.9 ``jax.make_mesh`` defaults to ``Explicit`` axes, whose
    sharding becomes part of every array type: a shard_map output would
    then carry its mesh sharding into the plain reshapes of the substrate
    and model code, which raises ``ShardingTypeError``. All meshes of this
    repo are built here.
    """
    return jax.make_mesh(
        tuple(shape), tuple(axes), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_devices: Optional[int] = None) -> Mesh:
    """Small ("data", "model") mesh over the first ``n_devices`` devices
    (default: all of them); "model" is 2 wide when the count is even."""
    n = n_devices or len(jax.devices())
    model = 2 if n % 2 == 0 else 1
    return make_mesh((n // model, model), ("data", "model"),
                     devices=jax.devices()[:n])


def contraction_partitioning(mesh: Mesh, *, m_axis: str = "data",
                             k_axis: Optional[str] = "model"):
    """Substrate :class:`~repro.nn.substrate.Partitioning` for this mesh.

    Data-parallel M over ``m_axis``, reduce-scattered K over ``k_axis``.
    An axis missing from the mesh is dropped (a data-only debug mesh still
    works, k-sharding simply off); multi-pod meshes keep M on the single
    data axis — the "pod" axis stays pure batch parallelism.
    """
    from repro.nn import substrate as psub

    m = m_axis if m_axis in mesh.axis_names else None
    k = k_axis if (k_axis and k_axis in mesh.axis_names) else None
    return psub.Partitioning(mesh, m_axis=m, k_axis=k)


# ---------------------------------------------------------------------------
# name-based parameter sharding rules
# ---------------------------------------------------------------------------

_FSDP = ("pod", "data")

# leaf-name -> PartitionSpec for the *trailing* dims (leading scan/stack dims
# are added as None automatically). Rules are matched on the last two path
# components, most-specific first.
_RULES = [
    (("router",), P(None, "model")),
    (("moe", "wi"), P("model", _FSDP, None)),
    (("moe", "wg"), P("model", _FSDP, None)),
    (("moe", "wo"), P("model", None, _FSDP)),
    (("wq", "w"), P(_FSDP, "model")),
    (("wk", "w"), P(_FSDP, "model")),
    (("wv", "w"), P(_FSDP, "model")),
    (("wo", "w"), P("model", _FSDP)),
    (("wi", "w"), P(_FSDP, "model")),
    (("wg", "w"), P(_FSDP, "model")),
    (("wz", "w"), P(_FSDP, "model")),
    (("wf", "w"), P(_FSDP, "model")),
    (("wo_gate", "w"), P(_FSDP, "model")),
    (("in_proj", "w"), P(_FSDP, "model")),
    (("out_proj", "w"), P("model", _FSDP)),
    (("patch_proj", "w"), P(_FSDP, "model")),
    (("emb",), P("model", _FSDP)),
    (("head", "w"), P(_FSDP, "model")),
]


def _path_names(path) -> list:
    names = []
    for entry in path:
        if hasattr(entry, "key"):
            names.append(str(entry.key))
        elif hasattr(entry, "idx"):
            names.append(str(entry.idx))
    return names


def _match_rule(names: list) -> Optional[P]:
    for pattern, spec in _RULES:
        lp = len(pattern)
        # match pattern against the tail of the name path (ignoring numeric
        # components, which come from lists/stacked structures)
        alpha = [n for n in names if not n.isdigit()]
        if tuple(alpha[-lp:]) == pattern:
            return spec
        # optimizer-state leaves live one level deeper (m/v/vr/vc)
        if alpha and alpha[-1] in ("m", "v", "vr", "vc") and \
                tuple(alpha[-lp - 1:-1]) == pattern:
            return spec
    return None


def _fit_spec(spec: P, shape, mesh: Mesh, path_names) -> P:
    """Right-align the rule to the leaf shape; drop non-dividing axes.

    Factored optimizer leaves (vr: rule minus last dim, vc: rule minus
    second-to-last) are handled by name.
    """
    dims = list(spec)
    leaf = path_names[-1] if path_names else ""
    if leaf == "vr":
        dims = dims[:-1]
    elif leaf == "vc":
        dims = dims[:-2] + dims[-1:] if len(dims) >= 2 else dims
    if len(dims) > len(shape):
        dims = dims[-len(shape):]
    full = [None] * (len(shape) - len(dims)) + dims
    out = []
    for size, d in zip(shape, full):
        if d is None:
            out.append(None)
            continue
        names = d if isinstance(d, tuple) else (d,)
        present = tuple(n for n in names if n in mesh.axis_names)
        prod = int(np.prod([mesh.shape[n] for n in present])) if present else 1
        if not present or size % prod != 0:
            out.append(None)
        else:
            out.append(present if len(present) > 1 else present[0])
    return P(*out)


def param_shardings(tree, mesh: Mesh):
    """NamedSharding tree for params (or optimizer state) by name rules."""
    def leaf(path, x):
        names = _path_names(path)
        spec = _match_rule(names)
        if spec is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, _fit_spec(spec, x.shape, mesh, names))

    return jax.tree_util.tree_map_with_path(leaf, tree)


def batch_shardings(tree, mesh: Mesh):
    """Inputs: batch dim over ("pod","data"), rest unsharded; scalars repl."""
    def leaf(_path, x):
        if not hasattr(x, "shape") or len(x.shape) == 0:
            return NamedSharding(mesh, P())
        present = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
        prod = int(np.prod([mesh.shape[n] for n in present]))
        if x.shape[0] % prod == 0:
            return NamedSharding(mesh, P(present, *([None] * (len(x.shape) - 1))))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(leaf, tree)


def cache_shardings(tree, mesh: Mesh):
    """Decode caches: shard the batch-like dim; stacked caches have a
    leading layer dim. SSM states (B, ...) shard dim 0; KV caches
    (L, B, S, H, dh) shard dim 1."""
    present = tuple(n for n in ("pod", "data") if n in mesh.axis_names)
    prod = int(np.prod([mesh.shape[n] for n in present]))

    def leaf(_path, x):
        if not hasattr(x, "shape") or len(x.shape) == 0:
            return NamedSharding(mesh, P())
        spec = [None] * len(x.shape)
        for dim in range(min(2, len(x.shape))):
            if x.shape[dim] % prod == 0:
                spec[dim] = present
                break
        else:
            # batch doesn't divide (e.g. long_500k batch=1): shard the
            # longest dim instead (sequence sharding of the KV cache)
            s, i = max((s, i) for i, s in enumerate(x.shape))
            if s % prod == 0:
                spec[i] = present
        # head dim of KV caches (ndim-2) over "model" when divisible
        if "model" in mesh.axis_names and len(x.shape) >= 4:
            hd = len(x.shape) - 2
            if spec[hd] is None and x.shape[hd] % mesh.shape["model"] == 0:
                spec[hd] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(leaf, tree)
