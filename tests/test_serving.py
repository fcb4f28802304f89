"""Serving subsystem: scheduling primitives, telemetry, the micro-batched
edge-detection service (bit-identical to ``edge_detect_batched`` on every
registered substrate), and the LM engine on the shared SlotScheduler."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import image_batch, mixed_shape_batch
from repro.models import registry as reg
from repro.nn import conv
from repro.nn import substrate as sub
from repro.nn.plan import SubstratePlan
from repro.serving import engine as engine_mod
from repro.serving import (EdgeDetectService, MicroBatcher, Request,
                           ServingEngine, ServingMetrics, SlotScheduler)
from tests.test_models_smoke import reduced
from tests.test_substrates import _tiny_cfg


# ---------------------------------------------------------------------------
# SlotScheduler (shared LM/vision scheduling core)
# ---------------------------------------------------------------------------


def test_slot_scheduler_refill_release_cycle():
    s = SlotScheduler(2)
    s.extend(["a", "b", "c"])
    assert s.refill() == [(0, "a"), (1, "b")]
    assert s.occupancy == 2 and s.busy and s.refill() == []
    s.release(0)
    assert s.refill() == [(0, "c")]
    assert [i for i, _ in s.occupied()] == [0, 1]
    s.release(0)
    s.release(1)
    assert not s.busy and s.occupancy == 0


def test_slot_scheduler_rejects_zero_slots():
    with pytest.raises(ValueError, match="n_slots"):
        SlotScheduler(0)


# ---------------------------------------------------------------------------
# MicroBatcher flush policy
# ---------------------------------------------------------------------------


def _echo_batcher(calls, **kw):
    def process(bucket, payloads):
        calls.append((bucket, list(payloads)))
        return [p * 10 for p in payloads]
    return MicroBatcher(process, **kw)


class FakeClock:
    """Injectable deterministic clock for timeout-policy tests.

    ``advance`` moves virtual time and wakes the batcher's workers (they
    block in ``cv.wait`` with a timeout computed from this clock), so
    timeout flushes fire exactly when the test says time has passed — no
    wall-clock sleeps, no flakes on slow machines."""

    def __init__(self):
        self.t = 0.0
        self._batcher = None

    def attach(self, batcher: MicroBatcher) -> MicroBatcher:
        self._batcher = batcher
        return batcher

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt
        if self._batcher is not None:
            with self._batcher._cv:
                self._batcher._cv.notify_all()


def test_flush_on_size_before_timeout():
    """A full bucket flushes immediately even with a huge max_wait."""
    calls = []
    with _echo_batcher(calls, max_batch_size=3, max_wait_s=60.0) as b:
        tickets = b.submit_many([1, 2, 3])
        assert [t.result(timeout=10.0) for t in tickets] == [10, 20, 30]
    assert calls == [(None, [1, 2, 3])]
    assert b.metrics.batches_by_reason == {"size": 1}
    assert b.metrics.occupancy_hist == {3: 1}


def test_flush_on_timeout_partial_batch():
    """A partial bucket flushes once its oldest request expires.

    Virtual time (FakeClock) — both submissions land at t=0, nothing may
    flush until the clock passes max_wait_s, then exactly one timeout
    batch fires. Deterministic on any machine."""
    calls = []
    clock = FakeClock()
    with clock.attach(_echo_batcher(calls, max_batch_size=8, max_wait_s=1.0,
                                    clock=clock)) as b:
        tickets = b.submit_many([1, 2])
        clock.advance(0.5)                       # before the deadline
        assert not any(t.done() for t in tickets)
        clock.advance(0.6)                       # past max_wait_s
        assert [t.result(timeout=10.0) for t in tickets] == [10, 20]
    assert calls == [(None, [1, 2])]
    assert b.metrics.batches_by_reason == {"timeout": 1}
    assert b.metrics.occupancy_hist == {2: 1}
    # latencies are measured on the injected clock: exact, not approximate
    assert all(t.latency_s == pytest.approx(1.1) for t in tickets)


def test_queue_wait_carries_every_wait_on_the_tracers_clock():
    """``batch.queue_wait`` holds each ticket's enqueue-to-pop wait, exact on
    the batcher's FakeClock, and ends at the tracer's now although the
    tracer runs on a clock 1000 s apart."""
    from repro.obs.trace import Tracer, tracing_scope

    calls = []
    clock = FakeClock()
    tracer_clock = FakeClock()
    tracer_clock.t = 1000.0
    tracer = Tracer(clock=tracer_clock)           # its zero is at 1000 s
    b = _echo_batcher(calls, max_batch_size=8, max_wait_s=60.0, clock=clock)
    b.submit(1)
    clock.advance(0.25)
    b.submit(2)
    clock.advance(0.5)
    b.submit(3)
    clock.advance(0.125)
    tracer_clock.t = 1007.0
    with tracing_scope(tracer):
        b.flush()
    (ev,) = [e for e in tracer.events() if e["name"] == "batch.queue_wait"]
    assert ev["args"]["waits_ms"] == [875.0, 625.0, 125.0]
    assert ev["args"]["size"] == 3 and ev["args"]["reason"] == "drain"
    assert ev["dur"] == pytest.approx(875e3)
    assert ev["ts"] + ev["dur"] == pytest.approx(7e6)   # the tracer's now


def test_bucket_isolation_and_sync_flush():
    """Buckets never mix inside a batch; flush() drains without a worker."""
    calls = []
    b = _echo_batcher(calls, max_batch_size=2, max_wait_s=60.0,
                      bucket_fn=lambda p: p % 2)
    tickets = b.submit_many([0, 1, 2, 3, 4])   # evens bucket 0, odds bucket 1
    assert b.depth == 5
    b.flush()
    assert [t.result(timeout=0) for t in tickets] == [0, 10, 20, 30, 40]
    for bucket, payloads in calls:
        assert {p % 2 for p in payloads} == {bucket}
    sizes = sorted(len(p) for _, p in calls)
    assert sizes == [1, 2, 2] and b.depth == 0
    assert b.metrics.batches_by_reason["size"] == 2   # two full pairs
    assert b.metrics.batches_by_reason["drain"] == 1  # the odd one out


def test_stop_drains_queue():
    calls = []
    b = _echo_batcher(calls, max_batch_size=8, max_wait_s=60.0).start()
    t = b.submit(7)
    b.stop(drain=True)
    assert t.result(timeout=0) == 70
    assert b.metrics.batches_by_reason == {"drain": 1}


def test_expired_bucket_not_starved_by_full_bucket():
    """Oldest flushable head wins: a continuously-full hot bucket must not
    preempt another bucket whose head has exceeded max_wait_s."""
    t = [0.0]
    calls = []
    b = MicroBatcher(lambda k, ps: [p for p in ps], max_batch_size=2,
                     max_wait_s=0.01, bucket_fn=lambda p: p[0],
                     clock=lambda: t[0])
    b.submit(("cold", 0))
    t[0] = 0.02                                   # cold head now expired
    b.submit(("hot", 1))
    b.submit(("hot", 2))                          # hot bucket is full
    ready = b._pop_ready_locked(t[0], drain=False)
    assert ready is not None
    key, batch, reason = ready
    assert key == "cold" and reason == "timeout" and len(batch) == 1


def test_submit_after_stop_raises():
    """A post-stop ticket would never be served — submit must fail fast."""
    b = _echo_batcher([], max_batch_size=2, max_wait_s=60.0).start()
    b.stop()
    with pytest.raises(RuntimeError, match="stopped"):
        b.submit(1)
    # restartable: start() clears the stopped state
    t = b.start().submit(2)
    b.stop(drain=True)
    assert t.result(timeout=0) == 20


def test_process_error_propagates_to_every_ticket():
    def boom(bucket, payloads):
        raise RuntimeError("kernel exploded")
    b = MicroBatcher(boom, max_batch_size=2, max_wait_s=60.0)
    tickets = b.submit_many([1, 2])
    b.flush()
    for t in tickets:
        with pytest.raises(RuntimeError, match="kernel exploded"):
            t.result(timeout=0)
    assert b.metrics.requests_failed == 2 and b.metrics.requests_served == 0


def test_concurrent_submitters_all_served():
    results = {}
    with _echo_batcher([], max_batch_size=4, max_wait_s=0.001) as b:
        def client(i):
            results[i] = b.submit(i).result(timeout=10.0)
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert results == {i: i * 10 for i in range(16)}
    assert b.metrics.requests_served == 16


# ---------------------------------------------------------------------------
# ServingMetrics
# ---------------------------------------------------------------------------


def test_metrics_counters_and_percentiles():
    m = ServingMetrics()
    for d in (3, 5, 2):
        m.record_enqueue(d)
    m.record_batch(3, "size", 4)
    m.record_batch(1, "timeout", 4)
    for lat in np.linspace(0.001, 0.1, 100):
        m.record_done(float(lat), depth=0)
    s = m.snapshot()
    assert s["requests_enqueued"] == 3 and s["requests_served"] == 100
    assert s["queue_depth_peak"] == 5 and s["queue_depth"] == 0
    assert s["batches_by_reason"] == {"size": 1, "timeout": 1}
    assert s["occupancy_hist"] == {1: 1, 3: 1}
    assert s["mean_occupancy"] == pytest.approx(0.5)
    assert s["latency_p50_ms"] == pytest.approx(50.5, rel=0.03)
    assert s["latency_p99_ms"] == pytest.approx(99.0, rel=0.03)
    assert s["latency_p95_ms"] <= s["latency_p99_ms"]
    assert m.throughput() > 0
    assert "p50=" in m.format_table()


def test_metrics_reset_zeroes_everything():
    m = ServingMetrics()
    m.record_enqueue(1)
    m.record_batch(2, "size", 2)
    m.record_done(0.5)
    m.reset()
    s = m.snapshot()
    assert s["requests_enqueued"] == 0 and s["batches_flushed"] == 0
    assert s["latency_p50_ms"] == 0.0 and s["occupancy_hist"] == {}


# ---------------------------------------------------------------------------
# EdgeDetectService: bit-identical to direct edge_detect_batched
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("spec", sorted(sub.list_substrates()))
def test_edge_service_bit_identical_per_substrate(spec):
    """Mixed-shape requests through bucketing/padding == direct pipeline."""
    imgs = mixed_shape_batch(5, shapes=((8, 8), (12, 10), (16, 16)), seed=2)
    svc = EdgeDetectService(spec, max_batch_size=2, max_wait_s=1e-3,
                            bucket_granularity=8)
    try:
        outs = svc.detect(imgs)
    finally:
        svc.close()
    for im, out in zip(imgs, outs):
        ref = np.asarray(conv.edge_detect_batched(im[None], spec))[0]
        assert out.shape == im.shape and out.dtype == np.uint8
        np.testing.assert_array_equal(out, ref, err_msg=f"{spec} {im.shape}")
    assert svc.metrics.requests_served == len(imgs)


def test_edge_service_non_proposed_pallas_spec_parity():
    """The generated closed-form Pallas kernel behind a full spec
    (wiring@width) serves bit-identically to the direct pipeline — the
    service carries any approx_pallas spec, not just proposed@8."""
    spec = "approx_pallas:design_strollo2020@4"
    imgs = mixed_shape_batch(4, shapes=((8, 8), (12, 10)), seed=4)
    svc = EdgeDetectService(spec, max_batch_size=2, max_wait_s=1e-3,
                            bucket_granularity=8)
    try:
        outs = svc.detect(imgs)
    finally:
        svc.close()
    assert svc.substrate.meta.cost_hint == "vpu"  # generated closed form
    for im, out in zip(imgs, outs):
        ref = np.asarray(conv.edge_detect_batched(im[None], spec))[0]
        np.testing.assert_array_equal(out, ref, err_msg=f"{spec} {im.shape}")


def test_edge_service_shape_bucket_isolation():
    """Images of different bucket shapes never share a flush."""
    svc = EdgeDetectService("exact", max_batch_size=8, max_wait_s=60.0,
                            bucket_granularity=8, start=False)
    svc.batcher.submit_many(mixed_shape_batch(
        6, shapes=((8, 8), (16, 16), (8, 8), (16, 16), (8, 8), (16, 16))))
    svc.batcher.flush()
    svc.close()
    # two buckets → two drain flushes of 3, despite room for 8
    assert svc.metrics.batches_flushed == 2
    assert svc.metrics.occupancy_hist == {3: 2}
    assert set(svc.compiled_shapes) == {(8, 8, 8), (8, 16, 16)}


def test_edge_service_flush_on_size_vs_drain():
    """5 images at max_batch 2: two full batches flush on size, the
    leftover is drained at close. max_wait is effectively infinite so the
    reason split never depends on wall-clock timing."""
    svc = EdgeDetectService("exact", max_batch_size=2, max_wait_s=60.0)
    try:
        tickets = [svc.submit(im) for im in image_batch(5, 16, 16)]
        full = [t.result(timeout=30.0) for t in tickets[:4]]  # size flushes
        assert all(o.shape == (16, 16) for o in full)
    finally:
        svc.close()                                # drains the leftover
    assert tickets[4].result(timeout=0).shape == (16, 16)
    assert svc.metrics.batches_by_reason == {"size": 2, "drain": 1}


def test_edge_service_compiled_call_cache_stable():
    """Same bucket shape served twice compiles once (batch-dim padding)."""
    svc = EdgeDetectService("exact", max_batch_size=4, max_wait_s=1e-3)
    try:
        svc.detect(image_batch(3, 16, 16))          # partial batch
        svc.detect(image_batch(4, 16, 16))          # full batch, same bucket
    finally:
        svc.close()
    assert svc.metrics.compiled_calls == 1
    assert svc.compiled_shapes == ((4, 16, 16),)


def test_edge_service_noise_and_uint8_roundtrip():
    imgs = image_batch(4, 16, 16, noise=8.0)
    assert imgs.dtype == np.uint8
    assert not np.array_equal(imgs, image_batch(4, 16, 16))
    svc = EdgeDetectService("approx_lut", max_batch_size=4, max_wait_s=1e-3)
    try:
        outs = svc.detect(imgs)
    finally:
        svc.close()
    ref = np.asarray(conv.edge_detect_batched(imgs, "approx_lut"))
    np.testing.assert_array_equal(np.stack(outs), ref)


class _DeviceValue:
    """Stands in for a dispatched batch; logs how delivery reads it."""

    def __init__(self, a):
        self.a, self.nbytes, self.calls = a, a.nbytes, []

    def block_until_ready(self):
        self.calls.append("block")
        return self

    def __array__(self, dtype=None, copy=None):
        self.calls.append("copy")
        return self.a


def test_edge_service_delivery_splits_the_copy_only_when_traced():
    """Untraced, delivery is one ``np.asarray`` (wait and copy together);
    under a tracer it blocks first and times the copy as ``edge.fetch``
    inside ``edge.wait``. Both crop the same maps."""
    from repro.obs.trace import Tracer, tracing_scope

    svc = EdgeDetectService("exact", start=False)
    a = np.arange(32, dtype=np.uint8).reshape(2, 4, 4)
    shapes = [(4, 4), (3, 2)]
    plain = _DeviceValue(a)
    outs = svc._finalize((16, 16), (plain, shapes))
    assert plain.calls == ["copy"]
    traced, tracer = _DeviceValue(a), Tracer()
    with tracing_scope(tracer):
        outs_traced = svc._finalize((16, 16), (traced, shapes))
    assert traced.calls == ["block", "copy"]
    for out, out_traced, (h, w), full in zip(outs, outs_traced, shapes, a):
        np.testing.assert_array_equal(out, full[:h, :w])
        np.testing.assert_array_equal(out_traced, out)
    ev = {e["name"]: e for e in tracer.events()}
    fetch, wait = ev["edge.fetch"], ev["edge.wait"]
    assert fetch["args"]["parent"] == "edge.wait"
    assert fetch["args"]["bytes"] == 32 and fetch["args"]["size"] == 2
    assert wait["ts"] <= fetch["ts"]
    assert fetch["ts"] + fetch["dur"] <= wait["ts"] + wait["dur"]


def test_edge_service_delivered_maps_own_their_memory():
    """A delivered map is copied out of its batch array, full-width crops
    too, so a map the caller keeps does not hold the whole batch."""
    svc = EdgeDetectService("exact", start=False)
    a = np.arange(48, dtype=np.uint8).reshape(3, 4, 4)
    shapes = [(4, 4), (3, 4), (2, 3)]
    for out, (h, w), full in zip(
            svc._finalize((16, 16), (_DeviceValue(a), shapes)), shapes, a):
        np.testing.assert_array_equal(out, full[:h, :w])
        assert not np.shares_memory(out, a)


@pytest.mark.parametrize("spec,path", [("approx_pallas", "fused"),
                                       ("approx_bitexact", "im2col")])
def test_edge_service_reports_its_conv_path(spec, path):
    """The service knows its conv kernel before the first batch, names it
    on the ``edge.compile``/``edge.execute`` spans and counts the batches
    the fused kernel serves."""
    from repro.obs.trace import Tracer, tracing_scope

    imgs = image_batch(4, 12, 10, seed=3)
    tracer = Tracer()
    with tracing_scope(tracer), EdgeDetectService(
            spec, max_batch_size=2, max_wait_s=1e-3) as svc:
        assert svc.conv_path == path
        svc.detect(list(imgs))
        svc.detect(list(imgs[:2]))
    spans = [e for e in tracer.events()
             if e["name"] in ("edge.compile", "edge.execute")]
    assert len(spans) == svc.metrics.batches_flushed >= 3
    assert {e["args"]["conv"] for e in spans} == {path}
    want = svc.metrics.batches_flushed if path == "fused" else 0
    assert svc.metrics.fused_batches == want
    assert svc.stats()["fused_batches"] == want


def test_edge_service_plan_and_partitioning_take_im2col():
    from repro.launch.mesh import make_mesh

    part = sub.Partitioning(make_mesh((1,), ("data",)), m_axis="data")
    plan = SubstratePlan(default="approx_pallas:proposed@8")
    for svc in (EdgeDetectService("approx_pallas", partitioning=part,
                                  start=False),
                EdgeDetectService(plan, start=False)):
        assert svc.conv_path == "im2col"


def test_edge_service_rejects_bad_inputs():
    svc = EdgeDetectService("exact", start=False)
    with pytest.raises(ValueError, match="uint8"):
        svc.submit(np.zeros((4, 4), np.float32))
    with pytest.raises(ValueError, match="single"):
        svc.submit(np.zeros((2, 4, 4), np.uint8))
    with pytest.raises(ValueError, match="bucket_granularity"):
        EdgeDetectService("exact", bucket_granularity=0)


def test_mixed_shape_batch_generator():
    imgs = mixed_shape_batch(7, seed=1, noise=3.0)
    assert len(imgs) == 7
    assert len({im.shape for im in imgs}) > 1
    assert all(im.dtype == np.uint8 and im.ndim == 2 for im in imgs)
    with pytest.raises(ValueError, match="non-empty"):
        mixed_shape_batch(2, shapes=())


# ---------------------------------------------------------------------------
# LM ServingEngine (on the shared SlotScheduler)
# ---------------------------------------------------------------------------


def _lm_bundle(seed=0):
    cfg = reduced("minitron-8b", n_layers=1, d_model=32, d_ff=64, vocab=64,
                  n_heads=2, n_kv_heads=2)
    bundle = reg._BUILDERS[cfg.family](cfg)
    return bundle, bundle.init_params(jax.random.PRNGKey(seed))


def test_serving_engine_generates():
    bundle, params = _lm_bundle()
    eng = ServingEngine(bundle, params, batch_size=2, max_len=64)
    reqs = [Request(prompt=[1, 2, 3], max_tokens=5),
            Request(prompt=[4, 5], max_tokens=4, temperature=0.7)]
    out = eng.generate(reqs)
    assert len(out[0].output) == 5 and len(out[1].output) == 4
    assert all(0 <= t < 64 for t in out[0].output + out[1].output)
    # the engine reports through the shared metrics schema
    assert eng.metrics.requests_served == 2
    assert eng.metrics.batches_by_reason.keys() == {"decode"}
    assert eng.metrics.latency_percentile(50) > 0


def test_serving_engine_redundant_generate_is_noop():
    """Re-submitting already-done requests must not spin decode steps."""
    bundle, params = _lm_bundle()
    eng = ServingEngine(bundle, params, batch_size=2, max_len=64)
    reqs = [Request(prompt=[1, 2], max_tokens=3)]
    eng.generate(reqs)
    steps = eng.metrics.batches_flushed
    eng.generate(reqs)                   # all requests already done
    assert eng.metrics.batches_flushed == steps


def test_serving_engine_truncated_request_counts_failed():
    """A request cut off by the max_len horizon lands in requests_failed."""
    bundle, params = _lm_bundle()
    eng = ServingEngine(bundle, params, batch_size=1, max_len=8)
    out = eng.generate([Request(prompt=[1, 2, 3], max_tokens=50)])[0]
    assert 0 < len(out.output) < 50      # truncated, not fully served
    assert eng.metrics.requests_failed == 1
    assert eng.metrics.requests_served == 0


def test_serving_greedy_matches_decode_loop():
    """Engine greedy output == manual decode_step loop (same caches)."""
    bundle, params = _lm_bundle(seed=3)
    prompt = [5, 9, 11]

    eng = ServingEngine(bundle, params, batch_size=1, max_len=32)
    out = eng.generate([Request(prompt=prompt, max_tokens=4)])[0].output

    state = bundle.init_decode_state(1, 32)
    toks = list(prompt)
    outs = []
    for i in range(len(prompt) + 3):
        tok = toks[i] if i < len(prompt) else outs[-1]
        batch = {"token": jnp.asarray([[tok]], jnp.int32),
                 "cache_len": jnp.asarray(i, jnp.int32)}
        logits, state = jax.jit(bundle.decode_step)(params, state, batch)
        if i >= len(prompt) - 1:
            outs.append(int(np.asarray(logits[0, 0]).argmax()))
    assert out == outs[:4], (out, outs)


def test_serving_engine_substrate_override():
    bundle = reg.build_bundle(_tiny_cfg())
    assert bundle.plan == SubstratePlan.uniform("exact")
    params = bundle.init_params(jax.random.PRNGKey(0))
    eng = ServingEngine(bundle, params, batch_size=1, max_len=32,
                        substrate="int8")
    assert eng.cfg.dot_plan == SubstratePlan.uniform("int8")
    assert eng.bundle.substrate is sub.get_substrate("int8")
    out = eng.generate([Request(prompt=[1, 2, 3], max_tokens=4)])
    assert len(out[0].output) == 4
    assert all(0 <= t < eng.cfg.vocab for t in out[0].output)


def test_serving_engine_accepts_registry_instance_rejects_custom():
    bundle = reg.build_bundle(_tiny_cfg())
    params = bundle.init_params(jax.random.PRNGKey(0))
    # a registry-produced instance is accepted and resolves to its spec
    eng = ServingEngine(bundle, params, batch_size=1, max_len=16,
                        substrate=sub.get_substrate("approx_lut"))
    assert eng.cfg.dot_plan == SubstratePlan.uniform("approx_lut:proposed")

    # a custom (unregistered) subclass would be silently swapped out by the
    # spec-string model path, so the engine must refuse it
    class Custom(sub.LutSubstrate):
        pass

    with pytest.raises(ValueError, match="does not match the registered"):
        ServingEngine(bundle, params, batch_size=1, max_len=16,
                      substrate=Custom("proposed"))


# ---------------------------------------------------------------------------
# Device sampling (sample_tokens) and the engine's per-loop keys
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("temps", [
    [0.0, 0.0, 0.0, 0.0, 0.0, 0.0],         # every row greedy
    [0.0, 1.0, -1.0, 0.7, 0.0, 2.0],        # greedy and sampled in one batch
])
def test_sample_tokens_greedy_rows_take_the_argmax(temps):
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(6, 300)).astype(np.float32)
    logits[0, [17, 250]] = logits[0].max() + 1.0      # a tie: first wins
    logits[1] = 0.0                                   # uniform when sampled
    temps = np.asarray(temps, np.float32)
    greedy = temps <= 0
    for k in range(5):
        got = np.asarray(engine_mod.sample_tokens(
            jnp.asarray(logits), jnp.asarray(temps), jax.random.PRNGKey(k), 0))
        assert got.dtype == np.int32 and got.shape == (6,)
        np.testing.assert_array_equal(got[greedy],
                                      logits.argmax(-1)[greedy])
        assert ((0 <= got) & (got < 300)).all()
    assert int(got[0]) == 17
    if not greedy[1]:     # a uniform row at T=1 does not sit on one index
        draws = {int(engine_mod.sample_tokens(
            jnp.asarray(logits), jnp.asarray(temps),
            jax.random.PRNGKey(k), 0)[1]) for k in range(20)}
        assert len(draws) > 1


@pytest.mark.parametrize("temp", [0.7, 1.0])
def test_sample_tokens_draws_softmax_over_temperature(temp):
    n = 20_000
    row = np.array([2.0, 1.0, 0.5, 0.0, -0.5, -1.0, -2.0, 3.0], np.float32)
    got = np.asarray(engine_mod.sample_tokens(
        jnp.asarray(np.tile(row, (n, 1))), jnp.full((n,), temp),
        jax.random.PRNGKey(11), 0))
    p = np.exp(row / temp - (row / temp).max())
    p /= p.sum()
    freq = np.bincount(got, minlength=8) / n
    sigma = np.sqrt(p * (1 - p) / n)
    assert (np.abs(freq - p) <= 4 * sigma).all(), (freq, p)


def test_sample_tokens_draws_afresh_at_every_step():
    """One loop's key with the step folded in: the same logits give other
    draws at another step, and the same draws at the same step."""
    logits, temps = jnp.zeros((8, 1000)), jnp.ones((8,))
    key = jax.random.PRNGKey(4)
    draws = [np.asarray(engine_mod.sample_tokens(logits, temps, key, t))
             for t in (0, 1, 0)]
    np.testing.assert_array_equal(draws[0], draws[2])
    assert (draws[0] != draws[1]).any()


def _sampled_requests(n, max_tokens=8):
    return [Request(prompt=[1, 2, 3], max_tokens=max_tokens, temperature=1.0)
            for _ in range(n)]


def test_serving_engine_same_seed_same_tokens():
    bundle, params = _lm_bundle()
    runs = []
    for seed in (5, 5, 6):
        eng = ServingEngine(bundle, params, batch_size=2, max_len=32,
                            seed=seed)
        runs.append([r.output for r in eng.generate(_sampled_requests(2))])
    assert runs[0] == runs[1]
    assert runs[0] != runs[2]


def test_serving_engine_worker_loops_draw_different_streams():
    """Two loops fed the same prompts at T=1 draw their own tokens; within
    a loop, the two slots draw their own too."""
    bundle, params = _lm_bundle()
    eng = ServingEngine(bundle, params, batch_size=2, max_len=32, seed=3)
    reqs = eng.generate(_sampled_requests(4), workers=2)
    # round-robin: loop 0 serves requests 0 and 2, loop 1 serves 1 and 3
    assert all(len(r.output) == 8 for r in reqs)
    assert reqs[0].output != reqs[1].output
    assert reqs[2].output != reqs[3].output
    assert reqs[0].output != reqs[2].output


def test_serving_engine_step_returns_device_logits_the_driver_can_read():
    """``_step`` hands back (batch, vocab) float32 logits; numpy stacks
    them and their column blocks, as the benchmark driver's check does."""
    bundle, params = _lm_bundle(seed=3)
    eng = ServingEngine(bundle, params, batch_size=2, max_len=16)
    state, steps = eng._init_state(), []
    decode = jax.jit(bundle.decode_step)
    want_state = bundle.init_decode_state(2, 16)
    for t, toks in enumerate(([1, 4], [2, 5], [3, 6])):
        tokens = np.asarray(toks, np.int64)
        logits, state = eng._step(state, tokens, t)
        assert jnp.asarray(logits) is logits.array      # no host round trip
        assert np.asarray(logits).shape == (2, 64)
        assert np.asarray(logits).dtype == np.float32
        want, want_state = decode(params, want_state, {
            "token": jnp.asarray(tokens[:, None], jnp.int32),
            "cache_len": jnp.asarray(t, jnp.int32)})
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(want[:, 0], np.float32))
        steps.append(logits)
    whole = np.stack(steps, axis=1)
    assert whole.shape == (2, 3, 64)
    blocks = [np.stack([x[:, lo:lo + 16] for x in steps], axis=1)
              for lo in range(0, 64, 16)]
    np.testing.assert_array_equal(np.concatenate(blocks, axis=-1), whole)
