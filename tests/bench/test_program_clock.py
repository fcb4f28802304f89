"""The program's spans on the trace's clock, and the readers that need them:
``edge_queue_wait_p95_ms``, ``edge_fetch_ms`` and ``idle_host.edge`` on a
hand-built trace with known answers, on a run of the parent (whose program
records no ``edge.fetch`` and no ``waits_ms``), and on a whole traced run
of the harness at CPU size."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from bench import run as brun
from bench import trace as btrace
from bench import work
from bench.metrics import _program_clock

from test_bench_correct import SEED, tiny

ROOT = Path(__file__).resolve().parents[2]
V5E = work.peaks("TPU v5 lite")
OFFSET_NS = 123_456_789_000.0        # trace clock minus the program's clock
DELAYS_NS = [2000, 3000, 4000, 2000, 5000]   # span start to annotation start
WINDOW_MS = (50, 1600)


def _read(metric, rec):
    spec = importlib.util.spec_from_file_location(
        "m", ROOT / "bench" / "metrics" / f"{metric}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def _span(name, start_ms, end_ms, **args):
    return {"name": name, "ts": start_ms * 1e3,
            "dur": (end_ms - start_ms) * 1e3, "args": args}


def _on_trace(ms, delay_ns=0):
    return int(ms * 1e6 + OFFSET_NS + delay_ns)


def hand_built(parent=False):
    """Five batches, 300 ms apart on the program's clock, and one batch.process
    span before the capture began (no annotation holds it). Each batch: 30 ms
    of dispatch with the device idle, the kernel on the device for 220 ms,
    delivery 2 ms after dispatch ends, the last 8 ms of its wait a
    device-to-host copy with the device idle, 2 ms of crop."""
    spans = [_span("batch.process", 0, 30),
             _span("batch.queue_wait", 5, 10, waits_ms=[999.0])]
    annotations, ops = [], []
    waits = []
    for k in range(5):
        b = 100 + 300 * k
        w = [40.0 + k, 20.0, 5.0]
        waits += w
        spans += [
            _span("batch.queue_wait", b - w[0] / 1e3, b,
                  **({} if parent else {"waits_ms": w})),
            _span("batch.process", b, b + 30),
            _span("batch.finalize", b + 32, b + 260),
            _span("edge.wait", b + 32, b + 258),
        ]
        if not parent:
            spans.append(_span("edge.fetch", b + 250, b + 258,
                               bytes=8 * 1088 * 1920, size=3))
        annotations.append(("bench.edge_dispatch",
                            _on_trace(b, DELAYS_NS[k]),
                            _on_trace(b + 29), {"frames": 3}))
        ops.append(("%closed_form_matmul.1 = s32[8]", _on_trace(b + 30),
                    _on_trace(b + 250), True))
    annotations.append(("bench.window", _on_trace(WINDOW_MS[0]),
                        _on_trace(WINDOW_MS[1]), {}))
    tr = btrace.Trace({"/device:TPU:0": ops},
                      sorted(annotations, key=lambda a: a[1]))
    rec = {"trace": tr, "trace_window": tr.window(), "spans": spans}
    return rec, waits


def test_alignment_recovers_a_known_offset():
    rec, _ = hand_built()
    offset, residual, batches = _program_clock.alignment(rec)
    assert batches == 5
    assert offset == pytest.approx(OFFSET_NS + np.median(DELAYS_NS), abs=1)
    assert residual == pytest.approx(max(DELAYS_NS) - np.median(DELAYS_NS),
                                     abs=1)
    fetch = _program_clock.spans(rec, "edge.fetch")
    assert [round(s) for s, _, _ in fetch] == [
        round(_on_trace(100 + 300 * k + 250) + np.median(DELAYS_NS))
        for k in range(5)]


def test_readers_read_known_idle_wait_and_fetch():
    rec, waits = hand_built()
    assert _read("edge_fetch_ms", rec) == pytest.approx(8.0)
    # the wait before the window (999 ms) is left out
    assert _read("edge_queue_wait_p95_ms", rec) == pytest.approx(
        float(np.percentile(waits, 95)))
    # 30 ms of dispatch and 10 ms of copy and crop per batch, device idle
    window_ms = WINDOW_MS[1] - WINDOW_MS[0]
    idle_host = _read("idle_host.edge", rec)
    assert idle_host == pytest.approx(100 * 5 * 40 / window_ms, abs=1e-5)
    assert idle_host <= _read("idle_share.edge", rec)


def test_readers_on_the_parent_and_without_a_trace():
    rec, _ = hand_built(parent=True)
    assert _read("edge_fetch_ms", rec) is None
    assert _read("edge_queue_wait_p95_ms", rec) is None
    # batch.process and batch.finalize are recorded by the parent too
    assert _read("idle_host.edge", rec) == pytest.approx(
        _read("idle_host.edge", hand_built()[0]))
    bare = {"spans": rec["spans"]}
    assert _program_clock.alignment(bare) is None
    for metric in ("edge_fetch_ms", "edge_queue_wait_p95_ms",
                   "idle_host.edge"):
        assert _read(metric, bare) is None


def test_a_traced_harness_run_reads_the_programs_spans(monkeypatch):
    """A whole ``--trace 1`` run at CPU size, through the edge driver: the
    fetch nests in the wait, ``edge_mfu`` still pairs each execute with its
    own wait, and the new readers find their spans (the CPU trace has no
    device plane, so ``idle_host.edge`` reads nothing)."""
    captured = {}
    run_metric = brun.read_metric

    def keep(name, rec):
        captured.update(rec)
        return run_metric(name, rec)

    monkeypatch.setattr(brun, "read_metric", keep)
    result, _ = brun.run(*tiny("edge-1080p.steady"), seed=SEED, seconds=0.5,
                         trace=True, peak=V5E)
    assert result["correct"]
    metrics = result["metrics"]
    assert metrics["edge_queue_wait_p95_ms"]["value"] >= 0
    assert metrics["edge_fetch_ms"]["value"] > 0
    assert "idle_host.edge" not in metrics

    spans = captured["spans"]
    waits = [s for s in spans if s["name"] == "edge.wait"]
    fetches = [s for s in spans if s["name"] == "edge.fetch"]
    assert len(fetches) == len(waits) > 0
    for w, f in zip(waits, fetches):
        assert f["args"]["parent"] == "edge.wait"
        assert f["args"]["depth"] == w["args"]["depth"] + 1
        assert w["ts"] <= f["ts"] and f["ts"] + f["dur"] <= w["ts"] + w["dur"]
        assert f["args"]["bytes"] == 8 * 48 * 80  # uint8 maps
    executes = [s for s in spans if s["name"] == "edge.execute"]
    pads = [s for s in spans if s["name"] == "edge.pad"]
    # every dispatch the trace holds is matched to its batch.process span
    _, _, batches = _program_clock.alignment(captured)
    assert batches == len(captured["trace"].spans("bench.edge_dispatch")) > 0
    # edge_mfu over each batch's own execute-to-wait, the single worker's
    # k-th execute with its k-th wait
    first = len(pads) - len(executes)      # a first call would be a compile
    ops = sum(work.conv(p["args"]["size"], 40, 72, 3, 3)[0]
              for p in pads[first:])
    secs = sum(w["ts"] + w["dur"] - e["ts"]
               for e, w in zip(executes, waits[first:])) / 1e6
    assert _read("edge_mfu", captured) == pytest.approx(
        100 * ops / secs / V5E["int8_ops_per_s"])
