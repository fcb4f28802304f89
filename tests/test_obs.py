"""Observability layer: registry, tracing, meters, export, integration.

Covers the PR-7 acceptance criteria: the disabled-telemetry no-op path
(bit-identical results, zero registry writes), span nesting and thread
isolation, Prometheus/Chrome-trace export schemas, execution-time metering
under jit, the online error probe against the offline LUT oracle, and one
end-to-end serving run yielding queue/compile/execute spans plus per-spec
contraction/energy counters in one combined registry dump.
"""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lut
from repro.nn import substrate as sub
from repro.nn.conv import edge_detect_batched
from repro.obs import (ContractionMeter, JsonlSink, MetricsRegistry, Tracer,
                       current_meter, current_tracer, pdp_per_mac_fj,
                       telemetry_scope, trace_span, tracing_scope,
                       write_chrome_trace, write_metrics)
from repro.serving.edge_service import EdgeDetectService
from repro.serving.metrics import ServingMetrics


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_labels_and_value(self):
        r = MetricsRegistry()
        c = r.counter("ops_total", "ops", ("kind",))
        c.labels(kind="a").inc()
        c.labels(kind="a").inc(3)
        c.labels(kind="b").inc(2)
        assert dict((l["kind"], v) for l, v in c.samples()) == \
            {"a": 4, "b": 2}

    def test_counter_rejects_negative(self):
        r = MetricsRegistry()
        c = r.counter("n_total")
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_set_inc_setmax(self):
        r = MetricsRegistry()
        g = r.gauge("depth")
        g.set(5)
        g.inc(-2)
        assert g.value() == 3
        g.set_max(10)
        g.set_max(4)  # ratchet: no decrease
        assert g.value() == 10

    def test_histogram_cumulative_buckets(self):
        r = MetricsRegistry()
        h = r.histogram("lat", buckets=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0):
            h.observe(v)
        (_, snap), = h.samples()
        assert snap["count"] == 4
        assert snap["sum"] == pytest.approx(5.555)
        assert snap["buckets"] == {0.01: 1, 0.1: 2, 1.0: 3}

    def test_get_or_create_same_family(self):
        r = MetricsRegistry()
        assert r.counter("x_total", "h") is r.counter("x_total")

    def test_type_mismatch_rejected(self):
        r = MetricsRegistry()
        r.counter("m")
        with pytest.raises(ValueError):
            r.gauge("m")
        r2 = MetricsRegistry()
        r2.counter("m", labelnames=("a",))
        with pytest.raises(ValueError):
            r2.counter("m", labelnames=("b",))

    def test_label_set_must_match_declaration(self):
        r = MetricsRegistry()
        c = r.counter("x_total", labelnames=("spec",))
        with pytest.raises(ValueError):
            c.labels(wrong="v")
        with pytest.raises(ValueError):
            c.inc()  # labeled family needs .labels(...)

    def test_prometheus_text_schema(self):
        r = MetricsRegistry()
        r.counter("ops_total", "operations", ("spec",)) \
            .labels(spec='a"b\\c').inc(2)
        r.histogram("lat_seconds", "latency", buckets=(0.5, 1.0)).observe(0.7)
        text = r.to_prometheus()
        assert "# HELP ops_total operations" in text
        assert "# TYPE ops_total counter" in text
        # label escaping: backslash and quote
        assert 'ops_total{spec="a\\"b\\\\c"} 2' in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.5"} 0' in text
        assert 'lat_seconds_bucket{le="1.0"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 1' in text
        assert "lat_seconds_sum 0.7" in text
        assert "lat_seconds_count 1" in text

    def test_json_roundtrip(self):
        r = MetricsRegistry()
        r.counter("a_total", "help a", ("x",)).labels(x="1").inc()
        doc = json.loads(json.dumps(r.to_json()))
        assert doc["a_total"]["type"] == "counter"
        assert doc["a_total"]["samples"] == \
            [{"labels": {"x": "1"}, "value": 1}]

    def test_reset(self):
        r = MetricsRegistry()
        c = r.counter("a_total")
        c.inc(5)
        r.reset()
        assert c.value() == 0

    def test_thread_safety(self):
        r = MetricsRegistry()
        c = r.counter("n_total", labelnames=("t",))
        def work():
            for _ in range(1000):
                c.labels(t="x").inc()
        threads = [threading.Thread(target=work) for _ in range(8)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        (_, v), = c.samples()
        assert v == 8000


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class TestTracer:
    def test_nesting_depth_and_parent(self):
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                pass
        inner, outer = t.events()  # inner closes first
        assert inner["name"] == "inner" and outer["name"] == "outer"
        assert inner["args"]["depth"] == 1
        assert inner["args"]["parent"] == "outer"
        assert outer["args"]["depth"] == 0
        assert inner["ts"] >= outer["ts"]
        assert inner["dur"] <= outer["dur"]

    def test_thread_isolation(self):
        t = Tracer()
        done = threading.Event()
        def worker():
            with t.span("worker_span"):
                pass
            done.set()
        with t.span("main_span"):
            th = threading.Thread(target=worker)
            th.start()
            th.join()
        assert done.is_set()
        by_name = {e["name"]: e for e in t.events()}
        # the worker's span does NOT nest under the main thread's stack
        assert by_name["worker_span"]["args"]["depth"] == 0
        assert "parent" not in by_name["worker_span"]["args"]
        assert by_name["worker_span"]["tid"] != by_name["main_span"]["tid"]

    def test_chrome_trace_schema(self):
        t = Tracer()
        with t.span("s", "cat", foo="bar"):
            pass
        t.event("retro", t._clock() - 0.01, 0.01)
        t.instant("marker")
        doc = json.loads(json.dumps(t.chrome_trace()))
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert len(evs) == 3
        for e in evs:
            assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        xs = [e for e in evs if e["ph"] == "X"]
        assert len(xs) == 2 and all(e["dur"] >= 0 for e in xs)
        assert xs[0]["args"]["foo"] == "bar"

    def test_jsonl_sink(self, tmp_path):
        p = tmp_path / "spans.jsonl"
        t = Tracer()
        with JsonlSink(p) as sink:
            t.add_sink(sink)
            with t.span("a"):
                pass
            t.instant("b")
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert [l["name"] for l in lines] == ["a", "b"]

    def test_ambient_scope(self):
        assert current_tracer() is None
        with trace_span("nothing"):  # no-op without a tracer
            pass
        t = Tracer()
        with tracing_scope(t):
            assert current_tracer() is t
            with trace_span("ambient"):
                pass
            with tracing_scope(None):  # nested None disables
                assert current_tracer() is None
        assert current_tracer() is None
        assert [e["name"] for e in t.events()] == ["ambient"]

    def test_span_is_mirrored_into_the_profiler(self, tmp_path):
        """A span shows on the profiler's host plane under its own name and
        attributes, as long as the tracer measured it."""
        import glob
        import time

        from jax.profiler import ProfileData

        t = Tracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with t.span("obs.mirrored", "test", size=3, bucket="48x80"):
                time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True)
        found = [ev for plane in ProfileData.from_file(path).planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events
                 if ev.name == "obs.mirrored"]
        assert len(found) == 1
        stats = {k: v for k, v in found[0].stats}
        assert stats["size"] == 3 and stats["bucket"] == "48x80"
        (span,) = t.events()
        profiled_us = (found[0].end_ns - found[0].start_ns) / 1e3
        assert abs(profiled_us - span["dur"]) < 1e3   # within 1 ms

    def test_disabled_span_is_shared_and_imports_nothing(self, monkeypatch):
        import builtins

        from repro.obs import trace as trace_mod

        imported = []
        real_import = builtins.__import__

        def spy(name, *args, **kwargs):
            imported.append(name)
            return real_import(name, *args, **kwargs)

        assert current_tracer() is None
        monkeypatch.setattr(builtins, "__import__", spy)
        span = trace_span("off", "test", size=1)
        with span:
            pass
        monkeypatch.undo()
        assert span is trace_mod._NULL_SPAN
        assert imported == []


# ---------------------------------------------------------------------------
# meters
# ---------------------------------------------------------------------------


SPEC = "approx_lut:proposed"


def _flush_callbacks():
    """Wait until every pending jax.debug.callback has run."""
    jax.effects_barrier()


class TestMeterPricing:
    def test_alias_resolves_to_same_price(self):
        assert pdp_per_mac_fj("csp_axc1") == \
            pdp_per_mac_fj("design_esposito2018")
        assert pdp_per_mac_fj("proposed") == pdp_per_mac_fj("proposed@8")

    def test_proposed_cheaper_than_exact(self):
        # the paper's headline: the proposed design undercuts exact PDP
        assert 0 < pdp_per_mac_fj("proposed") < pdp_per_mac_fj("exact")

    def test_width_scales_price(self):
        assert pdp_per_mac_fj("proposed@4") < pdp_per_mac_fj("proposed@8")


class TestMeterRecording:
    def test_disabled_path_no_writes_and_bit_identical(self):
        rng = np.random.default_rng(0)
        a = rng.integers(-128, 128, (8, 16), dtype=np.int32)
        b = rng.integers(-128, 128, (16, 4), dtype=np.int32)
        s = sub.get_substrate(SPEC)
        meter = ContractionMeter(error_probe=True)
        bare = np.asarray(s.dot_general(a, b))
        with telemetry_scope(meter):
            metered = np.asarray(s.dot_general(a, b))
        _flush_callbacks()
        after = np.asarray(s.dot_general(a, b))  # scope exited
        _flush_callbacks()
        assert np.array_equal(bare, metered)
        assert np.array_equal(bare, after)
        summ = meter.summary()
        assert summ[SPEC]["contractions"] == 1  # only the in-scope call
        assert summ[SPEC]["macs"] == 8 * 16 * 4

    def test_no_scope_means_empty_registry(self):
        rng = np.random.default_rng(1)
        a = rng.integers(-128, 128, (4, 8), dtype=np.int32)
        b = rng.integers(-128, 128, (8, 4), dtype=np.int32)
        meter = ContractionMeter(error_probe=True)
        assert current_meter() is None
        sub.get_substrate(SPEC).dot_general(a, b)
        _flush_callbacks()
        for fam in meter.registry.to_json().values():
            assert fam["samples"] == []

    def test_jit_counts_every_execution(self):
        rng = np.random.default_rng(2)
        a = rng.integers(-128, 128, (4, 8), dtype=np.int32)
        b = rng.integers(-128, 128, (8, 4), dtype=np.int32)
        s = sub.get_substrate(SPEC)
        f = jax.jit(lambda x, y: s.dot_general(x, y))
        meter = ContractionMeter()
        with telemetry_scope(meter):
            for _ in range(3):
                jax.block_until_ready(f(a, b))
            _flush_callbacks()
        assert meter.summary()[SPEC]["contractions"] == 3
        # compiled with a scope, executed without one: records nothing
        jax.block_until_ready(f(a, b))
        _flush_callbacks()
        assert meter.summary()[SPEC]["contractions"] == 3

    def test_energy_prices_through_unit_gate_model(self):
        rng = np.random.default_rng(3)
        a = rng.integers(-128, 128, (4, 8), dtype=np.int32)
        b = rng.integers(-128, 128, (8, 4), dtype=np.int32)
        meter = ContractionMeter()
        with telemetry_scope(meter):
            sub.get_substrate(SPEC).dot_general(a, b)
            _flush_callbacks()
        row = meter.summary()[SPEC]
        assert row["energy_pdp_fj"] == \
            pytest.approx(row["macs"] * pdp_per_mac_fj("proposed"))

    def test_exact_float_path_metered_without_probe(self):
        meter = ContractionMeter(error_probe=True)
        s = sub.get_substrate("exact")
        x = np.linspace(-1, 1, 32, dtype=np.float32).reshape(4, 8)
        w = np.linspace(-1, 1, 16, dtype=np.float32).reshape(8, 2)
        with telemetry_scope(meter):
            s.dot_general(x, w, sub.ContractionSpec(quant=sub.QuantPolicy()))
            _flush_callbacks()
        assert meter.summary()["exact:exact"]["contractions"] == 1
        assert meter.probe_moments() == {}  # exact backends are never probed

    def test_edge_detect_batched_bit_identical_under_scope(self):
        rng = np.random.default_rng(4)
        imgs = rng.integers(0, 256, (2, 16, 16), dtype=np.uint8)
        bare = np.asarray(edge_detect_batched(imgs, SPEC))
        meter = ContractionMeter(error_probe=True)
        with telemetry_scope(meter):
            metered = np.asarray(edge_detect_batched(imgs, SPEC))
        _flush_callbacks()
        assert np.array_equal(bare, metered)
        assert meter.summary()[SPEC]["macs"] == 2 * 16 * 16 * 9

    def test_fused_conv_path_metered(self):
        rng = np.random.default_rng(5)
        imgs = rng.integers(0, 256, (2, 16, 16), dtype=np.uint8)
        spec = "approx_pallas:proposed"
        bare = np.asarray(edge_detect_batched(imgs, spec))
        meter = ContractionMeter(error_probe=True)
        with telemetry_scope(meter):
            metered = np.asarray(edge_detect_batched(imgs, spec))
        _flush_callbacks()
        assert np.array_equal(bare, metered)
        row = meter.summary()[spec]
        # same MAC accounting as the im2col path: B*H*W pixels x 9 taps
        assert row["macs"] == 2 * 16 * 16 * 9
        assert meter.probe_moments(spec)["n"] > 0


class TestErrorProbe:
    def test_moments_match_offline_lut_oracle(self):
        """Online probe moments vs core.lut on a bitexact wiring.

        Operands drawn uniform over the full signed range, fresh every
        iteration. The probe measures products over a rows x cols cross of
        operand draws, so the mean's effective sample size is the operand
        count (the products are correlated through shared operands), not
        the product count — the tolerance uses that.
        """
        key = "proposed"
        s = sub.get_substrate(f"approx_lut:{key}")
        rows = cols = kk = 64
        iters = 4
        meter = ContractionMeter(error_probe=True, probe_rows=rows,
                                 probe_cols=cols, probe_k=kk, seed=7)
        rng = np.random.default_rng(11)
        with telemetry_scope(meter):
            for _ in range(iters):
                a = rng.integers(-128, 128, (rows, kk), dtype=np.int32)
                b = rng.integers(-128, 128, (kk, cols), dtype=np.int32)
                s.dot_general(a, b)
            _flush_callbacks()
        mom = meter.probe_moments(f"approx_lut:{key}")
        assert mom["n"] == rows * kk * cols * iters
        oracle = lut.error_moments(key)
        med_oracle = float(np.abs(lut.error_lut(key)).mean())
        n_eff = rows * kk * iters  # independent lhs operand draws
        tol = 6 * oracle["std"] / np.sqrt(n_eff)
        assert mom["mean"] == pytest.approx(oracle["mean"], abs=tol)
        assert mom["med"] == pytest.approx(med_oracle, rel=0.1)
        assert 0 < mom["max_ed"] <= oracle["max_abs"]

    def test_max_ed_bounded_by_oracle_for_other_wiring(self):
        key = "design_du2022"
        s = sub.get_substrate(f"approx_lut:{key}")
        meter = ContractionMeter(error_probe=True, probe_rows=32,
                                 probe_cols=32, seed=3)
        rng = np.random.default_rng(13)
        a = rng.integers(-128, 128, (32, 32), dtype=np.int32)
        b = rng.integers(-128, 128, (32, 32), dtype=np.int32)
        with telemetry_scope(meter):
            s.dot_general(a, b)
            _flush_callbacks()
        mom = meter.probe_moments(f"approx_lut:{key}")
        assert mom["max_ed"] <= lut.error_moments(key)["max_abs"]


# ---------------------------------------------------------------------------
# ServingMetrics on the registry
# ---------------------------------------------------------------------------


class TestServingMetricsRegistry:
    def test_snapshot_shape_unchanged(self):
        m = ServingMetrics()
        m.record_enqueue(3)
        m.record_batch(2, "size", 4)
        m.record_done(0.01, depth=1)
        m.record_compile()
        s = m.snapshot()
        assert s["requests_enqueued"] == 1
        assert s["batches_by_reason"] == {"size": 1}
        assert s["occupancy_hist"] == {2: 1}
        assert s["compiled_calls"] == 1
        assert isinstance(s["requests_served"], int)

    def test_prometheus_export_of_serving_counters(self):
        m = ServingMetrics()
        m.record_enqueue(1)
        m.record_done(0.002)
        text = m.registry.to_prometheus()
        assert "serving_requests_enqueued_total 1" in text
        assert "serving_requests_served_total 1" in text
        assert "serving_request_latency_seconds_count 1" in text

    def test_reset_only_touches_serving_families(self):
        reg = MetricsRegistry()
        other = reg.counter("substrate_contractions_total", "", ("spec",))
        other.labels(spec="x").inc(5)
        m = ServingMetrics(registry=reg)
        m.record_enqueue(1)
        m.reset()
        assert m.requests_enqueued == 0
        (_, v), = other.samples()
        assert v == 5

    def test_throughput_reads_under_lock(self):
        # functional regression guard for the unlocked-read fix: concurrent
        # reset()/throughput() must not raise or return garbage
        m = ServingMetrics()
        stop = threading.Event()
        errors = []
        def reader():
            try:
                while not stop.is_set():
                    assert m.throughput() >= 0.0
            except BaseException as e:  # noqa: BLE001
                errors.append(e)
        th = threading.Thread(target=reader)
        th.start()
        for _ in range(200):
            m.record_done(0.001)
            m.reset()
        stop.set()
        th.join()
        assert not errors


# ---------------------------------------------------------------------------
# export helpers
# ---------------------------------------------------------------------------


class TestExport:
    def test_write_metrics_suffix_dispatch(self, tmp_path):
        r = MetricsRegistry()
        r.counter("a_total").inc()
        prom = write_metrics(r, tmp_path / "m.prom")
        assert "# TYPE a_total counter" in prom.read_text()
        js = write_metrics(r, tmp_path / "m.json", extra={"note": "hi"})
        doc = json.loads(js.read_text())
        assert doc["note"] == "hi"
        assert doc["metrics"]["a_total"]["samples"][0]["value"] == 1

    def test_write_chrome_trace(self, tmp_path):
        t = Tracer()
        with t.span("s"):
            pass
        p = write_chrome_trace(t, tmp_path / "trace.json")
        doc = json.loads(p.read_text())
        assert doc["traceEvents"][0]["name"] == "s"


# ---------------------------------------------------------------------------
# end-to-end: one serving run, one combined dump
# ---------------------------------------------------------------------------


class TestServingIntegration:
    def test_serving_run_yields_spans_and_combined_registry(self):
        reg = MetricsRegistry()
        tracer = Tracer()
        meter = ContractionMeter(reg, error_probe=True)
        rng = np.random.default_rng(0)
        imgs = [rng.integers(0, 256, (16, 16), dtype=np.uint8)
                for _ in range(4)]
        with tracing_scope(tracer), telemetry_scope(meter):
            svc = EdgeDetectService(
                SPEC, max_batch_size=2, max_wait_s=0.5,
                metrics=ServingMetrics(registry=reg))
            outs = svc.detect(imgs)
            svc.close()
            _flush_callbacks()

        # (a) Chrome trace with queue/compile/execute spans
        doc = json.loads(json.dumps(tracer.chrome_trace()))
        names = {e["name"] for e in doc["traceEvents"]}
        # 2 same-shape batches: first compiles, second hits the jit cache
        assert {"batch.queue_wait", "batch.process", "edge.pad",
                "edge.compile", "edge.execute", "edge.crop"} <= names
        for e in doc["traceEvents"]:
            assert e["ph"] in ("X", "i") and e["ts"] >= 0

        # (b) one Prometheus dump with serving + substrate series
        text = reg.to_prometheus()
        assert "serving_requests_served_total 4" in text
        assert f'substrate_contractions_total{{spec="{SPEC}"' in text
        assert f'substrate_energy_pdp_fj_total{{spec="{SPEC}"' in text

        # (c) probe moments within the offline oracle's envelope
        mom = meter.probe_moments(SPEC)
        assert mom["n"] > 0
        assert mom["max_ed"] <= lut.error_moments("proposed")["max_abs"]

        # served maps bit-identical to the direct pipeline
        direct = np.asarray(edge_detect_batched(np.stack(imgs), SPEC))
        for o, d in zip(outs, direct):
            assert np.array_equal(o, d)
