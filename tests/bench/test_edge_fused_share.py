"""``edge_fused_share``: the share of the window's batches that the fused
conv kernel served, on hand-built spans and on a whole traced run of the
harness at CPU size, for a substrate with a fused kernel and one without."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import pytest

from bench import run as brun
from bench import work

from test_bench_correct import SEED, tiny

ROOT = Path(__file__).resolve().parents[2]
V5E = work.peaks("TPU v5 lite")


def _read(rec):
    spec = importlib.util.spec_from_file_location(
        "m", ROOT / "bench" / "metrics" / "edge_fused_share.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def _spans(*paths):
    out = [{"name": "edge.pad", "ts": 0.0, "dur": 1.0, "args": {"size": 1}}]
    for i, path in enumerate(paths):
        args = {"shape": "8x48x80"} if path is None else {"conv": path}
        out.append({"name": "edge.compile" if i == 0 else "edge.execute",
                    "ts": 10.0 * i, "dur": 1.0, "args": args})
    return out


@pytest.mark.parametrize("paths,share", [
    (("fused", "fused", "fused"), 100.0),
    (("im2col", "im2col"), 0.0),
    (("fused", "im2col", "fused", "fused"), 75.0),
])
def test_share_of_batches_on_the_fused_kernel(paths, share):
    assert _read({"spans": _spans(*paths)}) == pytest.approx(share)


def test_reads_nothing_where_the_program_names_no_conv_path():
    assert _read({"spans": _spans(None, None)}) is None
    assert _read({"spans": []}) is None
    assert _read({}) is None


@pytest.mark.parametrize("substrate,share", [("approx_pallas", 100.0),
                                             ("approx_bitexact", 0.0)])
def test_a_traced_harness_run_reads_the_share(substrate, share):
    bench, cell, config, traffic = tiny("edge-1080p.steady")
    config["substrate"] = substrate
    result, _ = brun.run(bench, cell, config, traffic, seed=SEED,
                         seconds=0.5, trace=True, peak=V5E)
    assert result["correct"]
    assert result["metrics"]["edge_fused_share"] == {"value": share,
                                                     "unit": "%"}
