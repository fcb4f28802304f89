"""Substrate plans: site resolution, scan dispatch, spec-form parity, bundles.

Bit-identity semantics tested here follow the trace structure:

* a uniform plan given as a spec string, a plan, or the plan's dict form
  builds the *same* traced graph, so their outputs are compared
  bit-for-bit;
* the scanned dispatch path vs an unrolled python-loop oracle are
  *different* traces of the same float math — XLA reassociates the
  quantize/rescale arithmetic differently under ``lax.scan`` (measured
  ~1.5e-05 even for uniform plans with no ``lax.switch`` involved), so
  those comparisons use a tight ``allclose``;
* the edge pipeline is integer-domain with exact accumulation, so planned
  (tap-group) vs whole-kernel edge maps compare bit-for-bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import common as cm
from repro.models import registry as reg
from repro.nn import conv
from repro.nn import plan as splan
from repro.nn import substrate as sub
from repro.obs.meter import ContractionMeter, telemetry_scope

RNG = np.random.default_rng(0)


# ---------------------------------------------------------------------------
# resolution rules
# ---------------------------------------------------------------------------


def test_resolution_precedence_exact_beats_glob():
    p = splan.SubstratePlan(default="exact", rules=(
        ("layer.*", "int8"),
        ("layer.3.attn.wq", "approx_bitexact:proposed@6"),
    ))
    assert p.resolve("layer.3.attn.wq") == "approx_bitexact:proposed@6"
    assert p.resolve("layer.3.attn.wk") == "int8"


def test_resolution_most_literal_glob_wins_regardless_of_order():
    rules = [("layer.*", "int8"), ("layer.3.attn.*", "approx_lut:proposed")]
    for ordering in (rules, rules[::-1]):
        p = splan.SubstratePlan(default="exact", rules=tuple(ordering))
        assert p.resolve("layer.3.attn.wq") == "approx_lut:proposed"
        assert p.resolve("layer.1.ffn.wg") == "int8"


def test_resolution_tie_goes_to_later_rule():
    p = splan.SubstratePlan(default="exact", rules=(
        ("layer.1.*", "int8"),
        ("*.attn.wq", "approx_lut:proposed"),  # same literal count (9)
    ))
    assert splan._specificity("layer.1.*") == splan._specificity("*.attn.wq")
    assert p.resolve("layer.1.attn.wq") == "approx_lut:proposed"


def test_resolution_unknown_site_falls_back_to_default():
    p = splan.SubstratePlan(default="approx_bitexact:proposed@8",
                            rules=(("conv.edge.*", "int8"),))
    assert p.resolve("layer.0.ffn.wo") == "approx_bitexact:proposed@8"
    assert p.resolve(None) == "approx_bitexact:proposed@8"


def test_resolution_cache_isolated_per_plan():
    # the lru cache keys on the (plan, site) pair: two plans assigning the
    # same site differently never bleed into each other
    a = splan.SubstratePlan(default="exact", rules=(("x.y", "int8"),))
    b = splan.SubstratePlan(default="exact",
                            rules=(("x.y", "approx_lut:proposed"),))
    assert a.resolve("x.y") == "int8"
    assert b.resolve("x.y") == "approx_lut:proposed"
    assert a.resolve("x.y") == "int8"  # a's cache entry survived b's


def test_plan_validates_specs():
    with pytest.raises(ValueError, match="unknown substrate backend"):
        splan.SubstratePlan(default="no_such_backend")
    with pytest.raises(ValueError, match="unknown substrate backend"):
        splan.SubstratePlan(rules=(("a.b", "mystery:proposed"),))
    with pytest.raises(ValueError):
        splan.SubstratePlan(rules=(("", "exact"),))
    # wirings are validated by the backend factories at resolution time
    p = splan.SubstratePlan(rules=(("a.b", "approx_lut:mystery_wiring"),))
    with pytest.raises(Exception):
        p.substrate_for("a.b")


def test_plan_json_and_dict_round_trip(tmp_path):
    p = splan.SubstratePlan(default="approx_bitexact:proposed@8", rules=(
        ("conv.edge.center", "approx_bitexact:proposed@6"),
        ("layer.*.ffn.*", "int8"),
    ))
    assert splan.SubstratePlan.from_json(p.to_json()) == p
    assert splan.as_plan(p.to_dict()) == p
    path = tmp_path / "plan.json"
    splan.save_plan(str(path), p)
    assert splan.load_plan(str(path)) == p
    assert splan.load_plan(str(tmp_path)) == p  # dir → dir/plan.json
    with pytest.raises(ValueError, match="newer than supported"):
        splan.SubstratePlan.from_dict({"version": 99, "default": "exact"})


def test_as_plan_accepts_spec_string_and_rejects_junk():
    p = splan.as_plan("int8")
    assert p.is_uniform and p.default == "int8"
    assert splan.as_plan(p) is p
    with pytest.raises(TypeError):
        splan.as_plan(42)


# ---------------------------------------------------------------------------
# site scopes + dispatch
# ---------------------------------------------------------------------------


def test_site_scope_composes_and_rejects_wildcards():
    with splan.site_scope("layer.3", "attn"):
        idx, sites = splan.current_sites("wq")
        assert idx is None and sites == ("layer.3.attn.wq",)
    assert splan.current_sites("wq") == (None, ("wq",))
    with pytest.raises(ValueError):
        splan.site_scope("layer.*").__enter__()


def test_scan_site_scope_yields_per_repeat_candidates_and_rejects_nesting():
    names = ("layer.0", "layer.1")
    with splan.scan_site_scope(jnp.asarray(0), names):
        idx, sites = splan.current_sites("ffn.wg")
        assert idx is not None
        assert sites == ("layer.0.ffn.wg", "layer.1.ffn.wg")
        with pytest.raises(RuntimeError, match="nested"):
            splan.scan_site_scope(jnp.asarray(0), names).__enter__()


def test_dispatch_static_when_repeats_agree():
    p = splan.SubstratePlan(default="exact", rules=(("layer.*", "int8"),))
    with splan.scan_site_scope(jnp.asarray(1), ("layer.0", "layer.1")):
        d = splan.dispatch(p, "attn.wq")
    assert d.index is None and d.branch_of is None
    assert d.groups == (("int8", "layer.*.attn.wq"),)


def test_dispatch_switch_groups_when_repeats_differ():
    p = splan.SubstratePlan(default="exact", rules=(
        ("layer.1.*", "int8"), ("layer.3.*", "int8"),))
    names = tuple(f"layer.{i}" for i in range(4))
    with splan.scan_site_scope(jnp.asarray(2), names):
        d = splan.dispatch(p, "ffn.wo")
    assert d.index is not None
    assert d.branch_of == (0, 1, 0, 1)
    specs = dict(zip([s for s, _ in d.groups], [l for _, l in d.groups]))
    assert set(specs) == {"exact", "int8"}


# ---------------------------------------------------------------------------
# model integration: spec-form parity, scan dispatch
# ---------------------------------------------------------------------------


def _tiny_cfg(**overrides):
    return reg.get_config("minitron-8b", n_layers=2, d_model=32, d_ff=64,
                          vocab=64, n_heads=2, n_kv_heads=2, attn_chunk=16,
                          loss_chunk=16, remat=False, **overrides)


def _prefill_logits(cfg):
    bundle = reg.build_bundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(1, cfg.vocab, (2, 16)), jnp.int32)}
    return np.asarray(bundle.prefill(params, batch), np.float32)


@pytest.mark.parametrize("spec", ["exact", "approx_bitexact", "approx_lut"])
def test_uniform_plan_bit_identical_across_spec_forms(spec):
    plan = splan.SubstratePlan.uniform(spec)
    by_string = _prefill_logits(_tiny_cfg(dot_plan=spec))
    np.testing.assert_array_equal(
        by_string, _prefill_logits(_tiny_cfg(dot_plan=plan)))
    np.testing.assert_array_equal(
        by_string, _prefill_logits(_tiny_cfg(dot_plan=plan.to_dict())))


def test_mixed_plan_under_scan_matches_python_loop_oracle():
    """The lax.switch dispatch selects the right substrate per scanned layer.

    The oracle applies the same per-layer assignment through an unrolled
    loop; scan-vs-loop float reassociation bounds the comparison (see
    module docstring), while the *wrong*-substrate failure mode is orders
    of magnitude larger (approx vs exact differ at O(1) in the logits).
    """
    mixed = splan.SubstratePlan(default="exact", rules=(
        ("layer.1.*", "approx_bitexact:proposed@8"),))
    cfg = _tiny_cfg(dot_plan=mixed)
    bundle = reg.build_bundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(1, cfg.vocab, (2, 16)), jnp.int32)}
    planned = np.asarray(bundle.prefill(params, batch), np.float32)

    exact = _prefill_logits(_tiny_cfg(dot_plan="exact"))
    approx = _prefill_logits(
        _tiny_cfg(dot_plan="approx_bitexact:proposed@8"))
    # the mixed plan is its own thing: neither all-exact nor all-approx
    assert np.abs(planned - exact).max() > 1e-3
    assert np.abs(planned - approx).max() > 1e-3

    # unrolled oracle: layer 1 approx, layer 0 exact, via leaf site scopes
    x = np.asarray(RNG.normal(size=(2, 8, 32)), np.float32)
    w = np.asarray(RNG.normal(size=(2, 32, 32)), np.float32)
    cfg_m = dataclasses.replace(cfg, dot_plan=mixed)

    def scan_fwd(x0):
        names = ("layer.0", "layer.1")

        def body(c, xs):
            wi, i = xs
            with splan.scan_site_scope(i, names):
                return cm.dense(cfg_m, c, wi, site="proj"), None
        return jax.lax.scan(body, x0, (jnp.asarray(w), jnp.arange(2)))[0]

    def loop_fwd(x0):
        c = jnp.asarray(x0)
        for i in range(2):
            with splan.site_scope(f"layer.{i}"):
                c = cm.dense(cfg_m, c, jnp.asarray(w[i]), site="proj")
        return c

    a, b = np.asarray(scan_fwd(x)), np.asarray(loop_fwd(x))
    np.testing.assert_allclose(a, b, atol=1e-4, rtol=0)


def test_registry_bundle_carries_plan_and_default_substrate():
    mixed = splan.SubstratePlan(default="int8",
                                rules=(("layer.0.*", "exact"),))
    bundle = reg.build_bundle(_tiny_cfg(dot_plan=mixed))
    assert bundle.plan == mixed
    assert bundle.substrate is sub.get_substrate("int8")


# ---------------------------------------------------------------------------
# planned edge detection + per-site telemetry
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", [
    "approx_bitexact:proposed@8", "approx_bitexact:proposed@6",
    "approx_lut:design_du2022", "int8", "exact",
])
def test_uniform_planned_edge_bit_identical_to_batched(spec):
    imgs = np.asarray(RNG.integers(0, 256, (3, 24, 20)), np.uint8)
    direct = np.asarray(conv.edge_detect_batched(imgs, spec))
    planned = np.asarray(conv.edge_detect_planned(
        imgs, splan.SubstratePlan.uniform(spec)))
    np.testing.assert_array_equal(direct, planned)


def test_mixed_planned_edge_differs_and_is_deterministic():
    imgs = np.asarray(RNG.integers(0, 256, (2, 24, 24)), np.uint8)
    mixed = splan.SubstratePlan(
        default="approx_bitexact:proposed@8",
        rules=(("conv.edge.center", "approx_bitexact:proposed@6"),))
    uniform = np.asarray(conv.edge_detect_planned(
        imgs, splan.SubstratePlan.uniform("approx_bitexact:proposed@8")))
    a = np.asarray(conv.edge_detect_planned(imgs, mixed))
    b = np.asarray(conv.edge_detect_planned(imgs, mixed))
    np.testing.assert_array_equal(a, b)
    assert (a != uniform).any()


def test_per_site_energy_visible_in_metrics_export():
    imgs = np.asarray(RNG.integers(0, 256, (2, 16, 16)), np.uint8)
    mixed = splan.SubstratePlan(
        default="approx_bitexact:proposed@8",
        rules=(("conv.edge.center", "approx_bitexact:proposed@6"),))
    meter = ContractionMeter()
    with telemetry_scope(meter):
        np.asarray(conv.edge_detect_planned(imgs, mixed))
    sites = meter.site_summary()
    assert set(conv.edge_tap_sites()) <= set(sites)
    center = sites["conv.edge.center"]
    ring = sites["conv.edge.ring"]
    assert center["specs"] == [
        sub.get_substrate("approx_bitexact:proposed@6").meta.spec]
    assert ring["specs"] == [
        sub.get_substrate("approx_bitexact:proposed@8").meta.spec]
    assert ring["macs"] == 8 * center["macs"]  # 8 ring taps vs 1 center tap
    assert center["energy_pdp_fj"] > 0 and ring["energy_pdp_fj"] > 0
    # and the labeled series survive into the registry export
    export = meter.registry.to_json()
    assert "conv.edge.center" in str(export)


def test_lm_site_labels_reach_meter_through_scan():
    cfg = _tiny_cfg(dot_plan="exact")
    bundle = reg.build_bundle(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(1, cfg.vocab, (2, 16)), jnp.int32)}
    meter = ContractionMeter()
    with telemetry_scope(meter):
        np.asarray(bundle.prefill(params, batch))
    sites = set(meter.site_summary())
    # scanned layers condense to a glob label; leaves stay distinguishable
    # (minitron's squared-ReLU MLP has wi/wo and no gate; its untied head
    # is the top-level site lm_head)
    assert any(s.endswith("attn.wq") for s in sites), sites
    assert any(s.endswith("ffn.wi") for s in sites), sites
    assert "lm_head" in sites, sites
