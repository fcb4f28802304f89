"""``correct`` at a size a CPU test run holds: the harness drives a whole
run (set-up, window, reference check) with its look for a chip skipped, and
``correct`` comes out true for the program, false for the configuration's
control, and false for each fault the cell can have: an answer altered
where it is produced, and half of a batch left out.

The cell keeps its traffic kind and its reference; the frames are cut to
CPU size, and the substrates are the bit-exact jnp models of the same
multiplier (``approx_bitexact``), which the Pallas kernels equal bit for
bit.
"""
from __future__ import annotations

import copy

import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as brun
from bench import work
from bench.faults import FAULTS
from bench.reference import edge as ref_edge
from bench.reference import multiplier as ref_mult

V5E = work.peaks("TPU v5 lite")
SEED = 2**31 + 99


def tiny(workload):
    bench, cell, config, traffic = brun.load_cell(workload)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    traffic["frames"] = [{"height": 40, "width": 72, "share": 1}]
    traffic["pool_per_resolution"] = 3
    traffic["rate_per_s"] = 12.0
    config["substrate"] = "approx_bitexact"
    config["control_substrate"] = "approx_bitexact:proposed@7"
    return bench, cell, config, traffic


def run_tiny(workload, control=False, seed=SEED):
    result, checks = brun.run(*tiny(workload), seed=seed, seconds=0.5,
                              trace=False, control=control, peak=V5E)
    return result


# -- the references agree with the program where they must ---------------------


def test_multiplier_table_equals_the_programs_model():
    from repro.core import multiplier as pm

    v = np.arange(-128, 128)
    want = pm.approx_multiply(jnp.asarray(v[:, None]), jnp.asarray(v[None, :]))
    np.testing.assert_array_equal(ref_mult.table(), np.asarray(want))


def test_approx_dot_equals_the_programs_bitexact_contraction():
    from repro.nn import substrate as sub

    rng = np.random.default_rng(0)
    a = rng.integers(-127, 128, (24, 96)).astype(np.int8)
    b = rng.integers(-127, 128, (96, 40)).astype(np.int8)
    want = sub.get_substrate("approx_bitexact").dot_int(a, b)
    got = ref_mult.approx_dot(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_edge_reference_equals_the_programs_tap_loop():
    from repro.nn import conv

    rng = np.random.default_rng(1)
    img = rng.integers(0, 256, (19, 33)).astype(np.uint8)
    np.testing.assert_array_equal(
        ref_edge.edge_map(img, conv.LAPLACIAN),
        np.asarray(conv.edge_detect(img, "proposed")))


# -- sound runs, control and faults --------------------------------------------


@pytest.mark.parametrize("workload", ["edge-1080p.steady"])
def test_program_is_correct_and_control_is_not(workload):
    sound = run_tiny(workload)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 0
    control = run_tiny(workload, control=True)
    assert not control["correct"]
    for name, c in control["checks"].items():
        assert c["value"] > sound["checks"][name]["value"]


@pytest.mark.parametrize("workload,fault", [
    ("edge-1080p.steady", "altered_answer"),
    ("edge-1080p.steady", "half_batch"),
])
def test_planted_fault_is_not_correct(workload, fault):
    with FAULTS[fault]():
        r = run_tiny(workload)
    assert r["attempted"] > 0 and not r["correct"]
