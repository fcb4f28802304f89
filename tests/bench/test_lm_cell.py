"""The ``lm-decode.approx`` cell at a size a CPU test run holds: the
harness drives whole runs (set-up, window of waves, reference check), and
``correct`` comes out true for the program and false for the
configuration's control and for each fault of ``bench/faults_lm.py``; the
waves carry the same lengths on every seed; the per-layer readers read a
traced run; the roofline's work is counted from shapes.

The cell keeps its traffic kind, its block, its precision and its
reference; the widths, depth, vocabulary and lengths are cut to CPU size,
and the substrates are the bit-exact jnp model of the same multiplier
(``approx_bitexact``), which the Pallas kernels equal bit for bit.
"""
from __future__ import annotations

import copy
import importlib.util
from pathlib import Path

import numpy as np
import pytest

from bench import lm_work, work
from bench import run as brun
from bench.arrivals import waves
from bench.drivers import lm as dlm
from bench.faults_lm import FAULTS

ROOT = Path(__file__).resolve().parents[2]
V5E = work.peaks("TPU v5 lite")
CELL = "lm-decode.approx"
SEED = 2**31 + 77


def tiny():
    bench, cell, config, traffic = brun.load_cell(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["model"].update(n_layers=2, d_model=64, n_heads=6, n_kv_heads=2,
                           head_dim=16, d_ff=128, vocab=512)
    config["substrate"] = "approx_bitexact"
    config["control_substrate"] = "approx_bitexact:proposed@7"
    config["engine"] = {"batch_size": 8, "max_len": 24}
    traffic.update(waves=2, prompt_tokens=[2, 6], output_tokens=[3, 9])
    return bench, cell, config, traffic


def run_tiny(control=False, trace=False, seed=SEED):
    return brun.run(*tiny(), seed=seed, seconds=5.0, trace=trace,
                    control=control, peak=V5E)[0]


# at this size a float32 last-bit difference moves an 8-bit code on about
# half the seeds (3000000000 and 3000000003 among them), which carries on
# through every later layer to the logits; in the first layer's KV cache a
# moved code moves only itself
@pytest.mark.parametrize("seed", [SEED, 3000000000, 3000000003])
def test_program_is_correct(seed):
    r = run_tiny(seed=seed)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 16              # both waves, 8 requests each
    kv = r["checks"]["kv_layer0_rel_l2"]
    assert kv["value"] < kv["limit"] / 10
    # end to end: setup and the decode steps as frames
    assert set(r["metrics"]) == {"setup_s", "frames_per_s", "frame_p95_ms"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


def test_control_is_not_correct():
    control = run_tiny(control=True)
    assert not control["correct"]
    for name, c in control["checks"].items():
        assert c["value"] > c["limit"], name
    # the first layer's cache misses its limit many times over
    kv = control["checks"]["kv_layer0_rel_l2"]
    assert kv["value"] >= 3 * kv["limit"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    with FAULTS[fault]():
        r = run_tiny()
    assert r["attempted"] > 0 and not r["correct"]


@pytest.mark.parametrize("seed", [0, 2**31 + 5, 3000000019])
def test_waves_hold_the_same_lengths_on_every_seed(seed):
    traffic = brun.load_cell(CELL)[3]
    got = waves.schedule(traffic, 51.0, np.random.default_rng(seed))
    want = sorted(zip(waves.quantiles(8, 32, 8), waves.quantiles(32, 96, 8)))
    assert len(got) == traffic["waves"]
    for wave in got:
        assert len(wave) == traffic["batch"]
        assert sorted(p for p, _ in wave) == sorted(p for p, _ in want)
        assert sorted(o for _, o in wave) == sorted(o for _, o in want)
        assert max(p + o for p, o in wave) < brun.load_cell(CELL)[2][
            "engine"]["max_len"]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "m", ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_traced_run_reads_the_program_metrics():
    r = run_tiny(trace=True)
    assert r["correct"]
    m = r["metrics"]
    for name in ("lm_tokens_per_s", "lm_step_ms", "lm_host_ms"):
        assert m[name]["value"] > 0, name
    # no TPU in the trace: the device readers read nothing
    assert "lm_kernel_roofline" not in m and "idle_share.lm" not in m


def test_host_readers_on_hand_built_spans():
    spans = [{"name": "serve.decode_step", "dur": d, "args": {"occupancy": 8}}
             for d in (100.0, 300.0, 200.0)]
    spans += [{"name": "lm.fetch", "dur": 30.0}, {"name": "lm.sample", "dur": 60.0}]
    rec = {"spans": spans, "lm_tokens_in_window": 51, "seconds": 5.1}
    assert _reader("lm_step_ms")(rec) == pytest.approx(0.2)
    assert _reader("lm_host_ms")(rec) == pytest.approx(0.03)
    assert _reader("lm_tokens_per_s")(rec) == pytest.approx(10.0)
    assert _reader("lm_step_ms")({"spans": []}) is None


def test_frame_metrics_on_hand_built_times():
    # steps start at 1, 2, 4 and 5 s of a window opened at 0; the last ends by 6
    got = dlm.frame_metrics([1.0, 2.0, 4.0, 5.0], 0.0, 6.0)
    assert got["frames_per_s"] == pytest.approx(4 / 6)
    assert got["frame_p95_ms"] == pytest.approx(
        np.percentile([1.0, 2.0, 1.0, 1.0], 95) * 1e3)
    assert dlm.frame_metrics([], 0.0, 6.0) == {}


def test_step_work_counts_every_projection_once():
    cfg = dlm.model_config(brun.load_cell(CELL)[2])
    layer = 4096 * (6144 + 1024 + 1024) + 6144 * 4096 + 2 * 4096 * 16384
    weights = 4 * layer + 4096 * 256000
    assert layer == 192_937_984
    ops, nbytes = lm_work.step(cfg, rows=8)
    assert ops == 2 * 8 * weights
    outputs = 4 * (6144 + 1024 + 1024 + 4096 + 16384 + 4096) + 256000
    inputs = 4 * (4096 * 3 + 6144 + 4096 + 16384) + 4096
    assert nbytes == weights + 8 * inputs + 4 * 8 * outputs
