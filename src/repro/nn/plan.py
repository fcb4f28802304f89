"""Per-site substrate plans: which multiplier runs *where* in a model.

A :class:`SubstratePlan` maps contraction **sites** — stable dotted names
like ``layer.3.attn.wq`` or ``conv.edge.center`` — to substrate specs
``backend[:mult_name[@N]]`` (the :mod:`repro.nn.substrate` grammar) — a
default rule plus glob-style overrides, so one model can run its
attention projections on ``approx_bitexact:proposed@8``, its FFN on a
cheaper width, and everything unnamed on the default.

Site names
----------

Sites are dotted paths pushed by the model code (:func:`site_scope`) around
each :func:`repro.models.common.dense` / conv contraction:

* LM / VLM:   ``layer.{i}.attn.{wq,wk,wv,wo}``, ``layer.{i}.ffn.{wg,wi,wo}``
  (``wi``/``wo`` only for the squared-ReLU MLP), ``layer.{i}.moe.shared.{…}``,
  ``patch_proj``, ``lm_head`` (an untied output head)
* enc-dec:    ``enc.{i}.attn.*``, ``dec.{i}.self.attn.*``,
  ``dec.{i}.cross.attn.*``, ``dec.{i}.cross.{wk,wv}``, ``dec.{i}.ffn.*``
* xLSTM:      ``layer.{i}.{mlstm,slstm}.{wq,…,wo}``
* zamba:      ``layer.{i}.mamba.{in_proj,out_proj}``, ``shared.attn.*``
* edge conv:  ``conv.edge`` (uniform path) and ``conv.edge.{center,ring}``
  (the planned tap-group path — see :func:`repro.nn.conv.edge_detect_planned`).

Resolution
----------

``plan.resolve(site)`` picks the **most specific** matching rule:

1. an exact (wildcard-free) pattern beats any glob;
2. among globs, the one with the most literal (non-wildcard) characters
   wins — ``layer.3.attn.*`` beats ``layer.*``;
3. exact ties go to the **later** rule (so appended overrides win);
4. no match → the plan default.

Patterns are :func:`fnmatch.fnmatchcase` globs; note ``*`` matches dots, so
``layer.*`` covers ``layer.3.attn.wq``. Resolution is lru-cached on the
(hashable) ``(plan, site)`` pair — per-call overhead after the first hit is
one dict lookup, same contract as ``get_substrate``.

Layers under ``lax.scan``
-------------------------

Stacked-parameter layers trace *once* for all repeats, so a per-layer
assignment cannot be baked into the traced spec string. The model body
wraps each scanned layer in :func:`scan_site_scope`, carrying the traced
repeat index plus the concrete per-repeat site names; :func:`dispatch` then
resolves every candidate site and either (a) collapses to one static
substrate when all repeats agree — the common case, zero runtime cost — or
(b) returns the distinct substrate groups plus a ``branch_of`` table the
caller lowers through ``jax.lax.switch`` on the carried index.
"""
from __future__ import annotations

import contextlib
import dataclasses
import fnmatch
import functools
import json
import os
import threading
from typing import Any, Dict, Iterable, Optional, Tuple

from repro.nn import substrate as psub

__all__ = [
    "SubstratePlan", "as_plan", "load_plan", "save_plan",
    "stat_spec", "stat_plan",
    "site_scope", "scan_site_scope", "current_sites", "dispatch",
    "plan_override_scope", "current_plan_override",
    "SiteDispatch", "PLAN_SCHEMA_VERSION",
]

PLAN_SCHEMA_VERSION = 1

_WILDCARDS = "*?["


def _check_spec(spec: str) -> str:
    """Eager spec validation: grammar + a registered backend name.

    Wirings/widths are validated lazily by the backend factories
    (``get_substrate``) — they own the per-backend width support matrix.
    """
    parts = psub.parse_spec(spec)
    known = psub.list_substrates()
    if parts.backend not in known:
        raise ValueError(
            f"plan names unknown substrate backend {parts.backend!r} "
            f"(known: {known})")
    return spec


def _norm_rules(rules) -> Tuple[Tuple[str, str], ...]:
    if isinstance(rules, dict):
        rules = tuple(rules.items())
    out = []
    for rule in rules:
        if isinstance(rule, dict):
            pat, spec = rule["site"], rule["spec"]
        else:
            pat, spec = rule
        pat, spec = str(pat), str(spec)
        if not pat:
            raise ValueError("plan rule has an empty site pattern")
        _check_spec(spec)
        out.append((pat, spec))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class SubstratePlan:
    """Site-addressed substrate assignment: default spec + glob overrides.

    default: substrate spec for sites no rule matches.
    rules:   ordered ``(site_pattern, spec)`` pairs; also accepts a dict or
             ``{"site": …, "spec": …}`` mappings at construction. Most
             specific pattern wins (see module docstring).

    Hashable by value, so plans key lru caches and can live on a (frozen)
    ``ModelConfig``.
    """

    default: str = "exact"
    rules: Tuple[Tuple[str, str], ...] = ()

    def __post_init__(self):
        _check_spec(self.default)
        object.__setattr__(self, "default", str(self.default))
        object.__setattr__(self, "rules", _norm_rules(self.rules))

    # -- resolution ----------------------------------------------------------

    def resolve(self, site: Optional[str]) -> str:
        """The substrate spec assigned to ``site`` (default when None)."""
        if site is None:
            return self.default
        return _resolve(self, str(site))

    def substrate_for(self, site: Optional[str]) -> psub.ProductSubstrate:
        return psub.get_substrate(self.resolve(site))

    @property
    def is_uniform(self) -> bool:
        return not self.rules

    @property
    def label(self) -> str:
        """Compact human-readable identity for logs/trace spans."""
        if self.is_uniform:
            return f"plan({self.default})"
        return f"plan({self.default}+{len(self.rules)} rules)"

    # -- construction / serialization ----------------------------------------

    @classmethod
    def uniform(cls, spec: str) -> "SubstratePlan":
        """One spec at every site."""
        return cls(default=str(spec))

    def to_dict(self) -> Dict[str, Any]:
        return {
            "version": PLAN_SCHEMA_VERSION,
            "default": self.default,
            "rules": [{"site": p, "spec": s} for p, s in self.rules],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "SubstratePlan":
        version = int(d.get("version", PLAN_SCHEMA_VERSION))
        if version > PLAN_SCHEMA_VERSION:
            raise ValueError(
                f"plan schema version {version} is newer than supported "
                f"({PLAN_SCHEMA_VERSION})")
        return cls(default=d.get("default", "exact"),
                   rules=_norm_rules(d.get("rules", ())))

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), **kw)

    @classmethod
    def from_json(cls, s: str) -> "SubstratePlan":
        return cls.from_dict(json.loads(s))


def as_plan(p: "SubstratePlan | str | dict") -> SubstratePlan:
    """Accept a plan, a spec string (→ uniform plan), or a plan dict."""
    if isinstance(p, SubstratePlan):
        return p
    if isinstance(p, str):
        return SubstratePlan.uniform(p)
    if isinstance(p, dict):
        return SubstratePlan.from_dict(p)
    raise TypeError(f"cannot interpret {type(p).__name__} as a SubstratePlan")


def save_plan(path: str, plan: SubstratePlan) -> str:
    """Write ``plan`` as JSON (see docs/plans.md for the schema)."""
    with open(path, "w") as f:
        json.dump(as_plan(plan).to_dict(), f, indent=2)
        f.write("\n")
    return path


def load_plan(path: str) -> SubstratePlan:
    """Read a plan from a JSON file, or from ``plan.json`` in a bundle dir."""
    if os.path.isdir(path):
        path = os.path.join(path, "plan.json")
    with open(path) as f:
        return SubstratePlan.from_dict(json.load(f))


# backends with an approx_stat statistical counterpart (same wiring + width)
_STAT_REWRITABLE = ("approx_bitexact", "approx_lut", "approx_pallas")


def stat_spec(spec: str) -> str:
    """A spec's fast statistical counterpart: same wiring/width, stat model.

    Used wherever a cheap stand-in for a bit-exact wiring is wanted — the
    autotuner's candidate scoring and the QAT ``forward="stat"`` training
    path both rewrite through here. Specs without a stat counterpart
    (``exact``, ``int8``, ``approx_stat`` itself) pass through unchanged.
    """
    parts = psub.parse_spec(spec)
    if parts.backend in _STAT_REWRITABLE:
        return f"approx_stat:{parts.mult_name}@{parts.width}"
    return spec


def stat_plan(plan: SubstratePlan) -> SubstratePlan:
    """``plan`` with every assignment rewritten via :func:`stat_spec`."""
    plan = as_plan(plan)
    return SubstratePlan(default=stat_spec(plan.default),
                         rules=tuple((p, stat_spec(s)) for p, s in plan.rules))


# ---------------------------------------------------------------------------
# rule matching (most-specific wins)
# ---------------------------------------------------------------------------


def _specificity(pattern: str) -> Tuple[int, int]:
    """(tier, literal-char count): exact patterns outrank every glob."""
    if not any(c in pattern for c in _WILDCARDS):
        return (2, len(pattern))
    literals = sum(1 for c in pattern if c not in _WILDCARDS)
    return (1, literals)


@functools.lru_cache(maxsize=None)
def _resolve(plan: SubstratePlan, site: str) -> str:
    best_spec, best_score = None, None
    for pattern, spec in plan.rules:
        if not fnmatch.fnmatchcase(site, pattern):
            continue
        score = _specificity(pattern)
        if best_score is None or score >= best_score:  # later rule wins ties
            best_spec, best_score = spec, score
    return plan.default if best_spec is None else best_spec


# ---------------------------------------------------------------------------
# ambient plan override (thread-local, mirrors partitioning_scope)
# ---------------------------------------------------------------------------


_PLAN_OVERRIDE_STATE = threading.local()


def current_plan_override() -> Optional[SubstratePlan]:
    """The ambient plan installed by :func:`plan_override_scope`, or None.

    Read at *trace* time by call sites that resolve their substrate from a
    config-carried plan (:func:`repro.models.common.substrate_plan`).
    """
    return getattr(_PLAN_OVERRIDE_STATE, "value", None)


@contextlib.contextmanager
def plan_override_scope(plan: "SubstratePlan | str | dict | None"):
    """Make ``plan`` govern every plan-consulting contraction in the block.

    While active, :func:`repro.models.common.substrate_plan` returns this
    plan instead of the model config's ``dot_plan`` — the hook
    by which a layer *above* an already-built model function (e.g. a
    :class:`repro.train.loop.TrainLoop` resuming from a checkpoint whose
    manifest pins different numerics) can change which substrate each site
    resolves to without rebuilding the model. Trace-time ambient: wrap the
    call being traced, exactly like
    :func:`repro.nn.substrate.dot_override_scope`. ``None`` is a no-op
    scope.
    """
    prev = getattr(_PLAN_OVERRIDE_STATE, "value", None)
    _PLAN_OVERRIDE_STATE.value = as_plan(plan) if plan is not None else None
    try:
        yield _PLAN_OVERRIDE_STATE.value
    finally:
        _PLAN_OVERRIDE_STATE.value = prev


# ---------------------------------------------------------------------------
# ambient site scopes (thread-local, mirrors partitioning_scope)
# ---------------------------------------------------------------------------


class _ScanFrame:
    """A scan-carried site segment: traced repeat index + per-repeat names."""

    __slots__ = ("index", "names")

    def __init__(self, index, names: Tuple[str, ...]):
        self.index = index
        self.names = names


_SITE_STATE = threading.local()


def _stack() -> list:
    st = getattr(_SITE_STATE, "stack", None)
    if st is None:
        st = _SITE_STATE.stack = []
    return st


@contextlib.contextmanager
def site_scope(*parts):
    """Push concrete site path segment(s) for the duration of the block.

    ``site_scope("layer.3", "attn")`` makes a ``dense(..., site="wq")``
    inside resolve at ``layer.3.attn.wq``. Segments must not contain glob
    wildcards (those belong in plan *rules*, not site names).
    """
    st = _stack()
    pushed = 0
    try:
        for p in parts:
            p = str(p)
            if not p or any(c in p for c in _WILDCARDS):
                raise ValueError(f"invalid site segment {p!r}")
            st.append(p)
            pushed += 1
        yield
    finally:
        del st[len(st) - pushed:]


@contextlib.contextmanager
def scan_site_scope(index, names: Iterable[str]):
    """Push a scan frame: traced repeat ``index`` selecting among ``names``.

    ``names[i]`` is the site segment the body occupies on repeat ``i``.
    At most one scan frame may be active (models scan one layer stack);
    nesting a second raises.
    """
    names = tuple(str(n) for n in names)
    if not names:
        raise ValueError("scan_site_scope needs at least one repeat name")
    st = _stack()
    if any(isinstance(e, _ScanFrame) for e in st):
        raise RuntimeError("nested scan_site_scope frames are not supported")
    st.append(_ScanFrame(index, names))
    try:
        yield
    finally:
        st.pop()


def current_sites(leaf: Optional[str] = None):
    """The candidate site names at this point, given a final ``leaf`` segment.

    Returns ``(scan_index, candidates)``: outside any scan frame the index
    is None and candidates has exactly one entry (possibly ``""`` when no
    scope is active and no leaf given); inside a frame there is one
    candidate per repeat, in repeat order.
    """
    pre, post, frame = [], [], None
    for entry in _stack():
        if isinstance(entry, _ScanFrame):
            frame = entry
        elif frame is None:
            pre.append(entry)
        else:
            post.append(entry)
    tail = post + ([str(leaf)] if leaf is not None else [])
    if frame is None:
        return None, (".".join(pre + tail),)
    return frame.index, tuple(".".join(pre + [n] + tail)
                              for n in frame.names)


# ---------------------------------------------------------------------------
# dispatch: plan × ambient sites → static substrate or switch groups
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SiteDispatch:
    """Resolved execution choice for one contraction call site.

    index:     None → static single-substrate call; otherwise the traced
               scan index to branch on.
    groups:    ``(spec, site_label)`` per distinct assignment (one entry
               when static). ``site_label`` is the meter attribution name
               (None → anonymous, falls back to the shape label).
    branch_of: per-repeat group id (len = number of scanned repeats), only
               when ``index`` is not None.
    """

    index: Any
    groups: Tuple[Tuple[str, Optional[str]], ...]
    branch_of: Optional[Tuple[int, ...]] = None


def _condense(names) -> str:
    """One display label covering several sites: common prefix + ``*``."""
    names = list(names)
    if len(set(names)) == 1:
        return names[0]
    prefix = os.path.commonprefix(names)
    reversed_suffix = os.path.commonprefix([n[::-1] for n in names])
    max_suffix = min(len(n) for n in names) - len(prefix)
    suffix = reversed_suffix[::-1][-max_suffix:] if max_suffix > 0 else ""
    return f"{prefix}*{suffix}"


def dispatch(plan: SubstratePlan, leaf: Optional[str] = None) -> SiteDispatch:
    """Resolve ``plan`` against the ambient site scopes for one call site."""
    index, candidates = current_sites(leaf)
    if index is None:
        site = candidates[0]
        return SiteDispatch(None, ((plan.resolve(site), site or None),))
    specs = [plan.resolve(c) for c in candidates]
    group_ids: Dict[str, int] = {}
    members: Dict[int, list] = {}
    branch_of = []
    for i, spec in enumerate(specs):
        gid = group_ids.setdefault(spec, len(group_ids))
        branch_of.append(gid)
        members.setdefault(gid, []).append(i)
    if len(group_ids) == 1:
        return SiteDispatch(None, ((specs[0], _condense(candidates)),))
    labels = {}
    for spec, gid in group_ids.items():
        label = _condense([candidates[i] for i in members[gid]])
        if label in labels.values():  # two groups condensed identically
            label = f"{label}#{gid}"
        labels[gid] = label
    groups = tuple((spec, labels[gid]) for spec, gid in group_ids.items())
    return SiteDispatch(index, groups, tuple(branch_of))
