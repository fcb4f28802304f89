"""The benchmark's yardstick on the CPU: work counts, traffic, the metric
arithmetic, the trace reduction on a trace recorded on a TPU v5e, and the
entry point's refusal to run without a chip."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import trace as btrace
from bench import work
from bench.arrivals import poisson
from bench.drivers import edge as edge_driver

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).with_name("data")
V5E = work.peaks("TPU v5 lite")


# -- work and peaks -----------------------------------------------------------


def test_peaks_table_names_v5e_and_refuses_other_kinds():
    assert V5E["int8_ops_per_s"] == 393e12
    assert V5E["bf16_ops_per_s"] == 197e12
    assert V5E["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v4")


def test_matmul_work_hand_count():
    ops, nbytes = work.matmul(8, 4096, 16384)
    assert ops == 2 * 8 * 4096 * 16384 == 1_073_741_824
    assert nbytes == 8 * 4096 + 4096 * 16384 + 4 * 8 * 16384 == 67_665_920


def test_conv_work_hand_count():
    ops, nbytes = work.conv(8, 1088, 1920, 3, 3)
    px = 8 * 1088 * 1920
    assert ops == 18 * px == 300_810_240
    assert nbytes == px + 9 + 4 * px == 83_558_409


def test_roofline_takes_the_larger_bound():
    # memory bound: 67.7 MB at 819 GB/s against 1.07 GOP at 393 TOP/s
    ops, nbytes = work.matmul(8, 4096, 16384)
    assert work.roofline_s(ops, nbytes, V5E, "int8") == nbytes / 819e9
    # compute bound: a square matmul
    ops, nbytes = work.matmul(8192, 8192, 8192)
    assert work.roofline_s(ops, nbytes, V5E, "bf16") == ops / 197e12


# -- traffic ------------------------------------------------------------------

EDGE = {"rate_per_s": 20.0, "pool_per_resolution": 8,
        "frames": [{"height": 1080, "width": 1920, "share": 1}]}
MIXED = {"rate_per_s": 20.0, "pool_per_resolution": 8,
         "frames": [{"height": 720, "width": 1280, "share": 1},
                    {"height": 480, "width": 640, "share": 1}]}
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("gen,traffic", [(poisson, EDGE), (poisson, MIXED)])
def test_open_loop_traffic_repeats_exactly_and_keeps_its_work(gen, traffic):
    a = gen.schedule(traffic, 40.0, np.random.default_rng(BIG_SEED))
    b = gen.schedule(traffic, 40.0, np.random.default_rng(BIG_SEED))
    c = gen.schedule(traffic, 40.0, np.random.default_rng(7))
    assert a == b
    assert a != c
    # another seed: the same number of frames, the same gaps and shares
    assert len(a) == len(c)
    gaps = lambda s: sorted(np.round(np.diff([0.0] + sorted({t for t, _, _ in s})), 9))  # noqa: E731
    assert gaps(a) == gaps(c)
    assert sorted(r for _, r, _ in a) == sorted(r for _, r, _ in c)
    assert max(t for t, _, _ in a) < 40.0


# -- end-to-end arithmetic ----------------------------------------------------


def test_frame_rate_and_p95_count_from_scheduled_arrival_through_a_stall():
    # 100 frames due every 0.1 s over 10 s, each served in 0.05 s, except a
    # 1 s stall at t=5 s that holds the frames due in [5, 6) until 6.05 s
    frames = []
    for i in range(100):
        due = 0.1 * i
        done = 6.05 if 5.0 <= due < 6.0 else due + 0.05
        frames.append({"due": due, "sent": due, "done": done})
    m = edge_driver.frame_metrics(frames, t_end=10.0, seconds=10.0)
    assert m["frames_per_s"] == 10.0
    lat = sorted(f["done"] - f["due"] for f in frames)
    assert m["frame_p95_ms"] == pytest.approx(np.percentile(lat, 95) * 1e3)
    # from the scheduled time, the stalled frames waited up to 1.05 s
    assert m["frame_p95_ms"] > 500
    # a frame never delivered is infinitely late, and not in the rate
    frames[-1]["done"] = None
    m = edge_driver.frame_metrics(frames, t_end=10.0, seconds=10.0)
    assert m["frames_per_s"] == 9.9


def test_stall_notes_name_the_late_submit_the_slow_batch_and_the_gc():
    t0 = 100.0
    frames = [{"due": t0 + 0.1 * i, "sent": t0 + 0.1 * i + (2.5 if i == 7 else 0.001)}
              for i in range(10)]
    # (dispatch start, dispatch end, deliver start, deliver end, frames)
    batches = [(t0 + 0.0, t0 + 0.02, t0 + 0.02, t0 + 0.26, 8),
               (t0 + 0.30, t0 + 0.32, t0 + 0.32, t0 + 2.90, 8),
               (t0 + 3.40, t0 + 3.41, t0 + 3.41, t0 + 3.65, 4)]
    pauses = [(0, t0 + 1.0, 0.002), (2, t0 + 2.0, 0.08), (0, t0 + 3.0, 0.001)]
    notes = edge_driver.stall_notes(frames, batches, pauses, t0)
    text = "\n".join(notes)
    assert "generator lateness: max 2500.000 ms at 0.700 s" in text
    assert "batches: 3" in text
    assert "longest delivery 2580.000 ms at 0.320 s" in text
    assert "longest gap between batches 500.000 ms at 3.400 s" in text
    assert "2/0/1 collections of generation 0/1/2" in text
    assert "longest 80.000 ms (generation 2) at 2.000 s" in text


# -- trace reduction ----------------------------------------------------------


def test_union_and_busy_merge_overlaps():
    assert btrace.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    tr = btrace.Trace({"/device:TPU:0": [("a", 0, 10, True), ("b", 5, 15, False),
                                         ("c", 20, 30, False)]},
                      [("bench.window", 0, 40, {}), ("bench.x", 14, 22, {})])
    assert btrace.busy_ns(tr, 0, 40) == 25
    assert btrace.busy_ns(tr, 8, 25) == 12
    bd = btrace.breakdown(tr, 0, 40)
    assert bd["device_ops"][0] == ["a", 10e-9]
    # a loop op holding others is not counted beside them
    loop = [("%while.1 = (...) while(...)", 0, 30, False), ("%run.1 = custom-call", 2, 9, True),
            ("%fusion.2 = f32[8]", 12, 20, False)]
    assert [o[0] for o in btrace.leaf_ops(loop)] == [loop[1][0], loop[2][0]]
    assert bd["idle_gaps"] == [["no benchmark span", 10e-9],
                               ["bench.x", 5e-9]]


# -- the entry point refuses to run without a chip -----------------------------


def _run_entry(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "edge-1080p.steady",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_refuses_without_a_tpu():
    r = _run_entry(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    r = _run_entry(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def _read(metric, rec):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "m", ROOT / "bench" / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def btrace_pairs(tr):
    from bench.metrics._common import pairs

    return pairs(tr.spans("bench.edge_dispatch"), tr.spans("bench.edge_deliver"))


def test_reduction_of_a_trace_recorded_on_a_v5e():
    # 3 s of edge-1080p.steady (20 frames/s), recorded on one TPU v5 lite
    tr = btrace.load(str(DATA / "edge-1080p.steady.xplane.pb"))
    assert list(tr.devices) == ["/device:TPU:0"]
    lo, hi = tr.window()
    assert (hi - lo) / 1e9 == pytest.approx(3.011, abs=1e-3)
    kernels = [(s, e) for _, s, e, k in tr.ops(lo, hi) if k]
    # one closed_form_matmul call per batch, ~237 ms each; nothing else
    assert len(kernels) == 10
    assert all(0.236 < (e - s) / 1e9 < 0.239 for s, e in kernels)
    busy = btrace.busy_ns(tr, lo, hi)
    assert 0.75 < busy / (hi - lo) < 0.9
    rec = {"trace": tr, "trace_window": (lo, hi), "peak": V5E,
           "config": json.loads(
               (ROOT / "bench/configs/edge-laplacian-3x3.json").read_text()),
           "traffic": json.loads(
               (ROOT / "bench/traffic/edge-1080p.steady.json").read_text())}
    assert _read("idle_share.edge", rec) == pytest.approx(
        100 * (1 - busy / (hi - lo)))
    # the work is the frames served at 1080x1920, not the 8x1088x1920
    # padded batch: 5 bytes a pixel at 819 GB/s bounds it
    batches = btrace_pairs(tr)
    served = [a["frames"] for (ds, _, a), (_, de, _) in batches
              if any(ds <= s < de for s, _ in kernels)]
    assert len(served) == len(kernels) and sum(served) < 8 * len(kernels)
    bound = sum(n * 1080 * 1920 * 5 + 9 for n in served)
    want = 100 * bound / 819e9 / (sum(e - s for s, e in kernels) / 1e9)
    assert _read("edge_kernel_roofline", rec) == pytest.approx(want)
    assert want < 0.05
    bd = btrace.breakdown(tr, lo, hi)
    assert bd["device_ops"][0][0] == "run.1"
    assert len(bd["idle_gaps"]) == 10
    assert sum(t for _, t in bd["idle_gaps"]) <= (hi - lo - busy) / 1e9 + 1e-9
