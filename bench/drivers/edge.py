"""Edge-detection service cells: open-loop frames into ``EdgeDetectService``.

The window submits each frame at its scheduled time from one thread
(``svc.submit``) and reads its delivery time from the ticket. Latency is
delivery minus the *scheduled* arrival, so a stall delays every frame
behind it. After the window every delivered map is compared with the plain
reference map of its frame (``bench.reference.edge``).
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import time

import numpy as np

from bench.reference import edge as ref_edge

LATE_LIMIT_S = 60.0  # a frame may be delivered this long after the window


def make_frames(rng, n: int, h: int, w: int) -> np.ndarray:
    """(n, h, w) uint8 frames: blocky regions (hard edges) under a smooth
    ramp and sensor noise, drawn from ``rng`` in bulk."""
    cell = 24
    gh, gw = -(-h // cell), -(-w // cell)
    blocks = rng.integers(0, 256, (n, gh, gw), dtype=np.int16)
    img = np.repeat(np.repeat(blocks, cell, 1), cell, 2)[:, :h, :w]
    ramp = (np.arange(w, dtype=np.int16) * 64 // w)[None, None, :]
    noise = rng.integers(-6, 7, (n, h, w), dtype=np.int16)
    return np.clip(img // 2 + ramp + noise, 0, 255).astype(np.uint8)


def setup(rec: dict) -> None:
    from repro.serving import EdgeDetectService

    cfg, traffic, rng = rec["config"], rec["traffic"], rec["rng"]
    rec["pools"] = [make_frames(rng, traffic["pool_per_resolution"],
                                f["height"], f["width"])
                    for f in traffic["frames"]]
    spec = cfg["control_substrate"] if rec["control"] else cfg["substrate"]
    rec["spec"] = spec
    svc = EdgeDetectService(spec, **cfg["service"])
    rec["service"] = svc
    for pool in rec["pools"]:          # every bucket shape the traffic uses
        for _ in range(2):
            svc.detect(list(pool), timeout=1200)
    arrivals = importlib.import_module(f"bench.arrivals.{traffic['kind']}")
    rec["schedule"] = arrivals.schedule(traffic, rec["seconds"], rng)
    prime_host_memory(window_bytes(rec["schedule"], traffic, cfg["service"]))
    svc.metrics.reset()
    rec["compiles_before"] = len(svc.compiled_shapes)


def window_bytes(schedule, traffic, service) -> int:
    """Host bytes the window keeps until its check: every delivered map is a
    view of its batch's host array (``max_batch_size`` frames of the bucket
    shape), counted here for batches that hold half of that on average or
    more."""
    g, b = service["bucket_granularity"], service["max_batch_size"]
    per_frame = [-(-f["height"] // g) * g * (-(-f["width"] // g) * g)
                 for f in traffic["frames"]]
    return sum(per_frame[r] for _, r, _ in schedule) * b // max(1, b // 2)


def prime_host_memory(nbytes: int) -> None:
    """Touch ``nbytes`` of host memory once and free it, in set-up. The
    pages then go back to the kernel already backed, and the window's own
    allocations reuse them. On a freshly started machine the first touch of
    a page is several times slower, and it would otherwise fall inside the
    window."""
    buf = np.ones(nbytes, np.uint8)
    del buf


@contextlib.contextmanager
def _annotated(svc, log: list):
    """Host annotations around each batch's dispatch and delivery, so the
    trace can tell which batch a kernel belongs to and what the host did;
    ``log`` gets ``(dispatch_start, dispatch_end, deliver_start,
    deliver_end, frames)`` of every batch on the host clock."""
    import jax

    b = svc.batcher
    process, finalize = b.process_fn, b.finalize_fn
    open_ = {}

    def dispatch(key, payloads):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(
                "bench.edge_dispatch", shape=f"{b.max_batch_size}x{key[0]}x{key[1]}",
                frames=len(payloads)):
            raw = process(key, payloads)
        open_[id(raw)] = (t, time.perf_counter(), len(payloads))
        return raw

    def deliver(key, raw):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.edge_deliver"):
            out = finalize(key, raw)
        log.append((*open_.pop(id(raw))[:2], t, time.perf_counter(), len(out)))
        return out

    b.process_fn, b.finalize_fn = dispatch, deliver
    try:
        yield
    finally:
        b.process_fn, b.finalize_fn = process, finalize


@contextlib.contextmanager
def _gc_pauses(log: list):
    """``log`` gets ``(generation, start, seconds)`` of every collection
    the garbage collector makes inside the block."""
    start = []

    def on_gc(phase, info):
        if phase == "start":
            start.append(time.perf_counter())
        elif start:
            t = start.pop()
            log.append((info["generation"], t, time.perf_counter() - t))

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)


def measure(rec: dict, trace_dir) -> None:
    import jax

    from repro.obs.trace import Tracer, tracing_scope

    svc, pools, seconds = rec["service"], rec["pools"], rec["seconds"]
    tracer = Tracer() if trace_dir else None
    frames, batches, pauses = [], [], []
    with contextlib.ExitStack() as stack:
        stack.enter_context(_annotated(svc, batches))
        stack.enter_context(_gc_pauses(pauses))
        if trace_dir:
            stack.enter_context(tracing_scope(tracer))
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter() + 0.01
        rec["t0"] = t0
        with jax.profiler.TraceAnnotation("bench.window"):
            for t, r, p in rec["schedule"]:
                due = t0 + t
                delay = due - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                sent = time.perf_counter()
                frames.append({"due": due, "sent": sent, "res": r, "pool": p,
                               "ticket": svc.submit(pools[r][p])})
            rest = t0 + seconds - time.perf_counter()
            if rest > 0:
                time.sleep(rest)
        t_end = t0 + seconds
        if trace_dir:
            jax.profiler.stop_trace()
    rec["t_end"] = t_end
    for f in frames:
        tk = f["ticket"]
        try:
            f["map"] = tk.result(timeout=max(0.0, t_end + LATE_LIMIT_S
                                             - time.perf_counter()))
            f["done"] = tk.enqueued_at + tk.latency_s
        except Exception as e:  # noqa: BLE001 - counted as failed below
            f["map"], f["done"], f["error"] = None, None, repr(e)
        del f["ticket"]
    rec["frames"] = frames
    m = svc.metrics
    rec["slots_used"] = m._slots_used.value()
    rec["slots_total"] = m._slots_total.value()
    rec["compiles_in_window"] = len(svc.compiled_shapes) - rec["compiles_before"]
    rec["spans"] = tracer.events() if tracer else []
    rec["attempted"] = len(frames)
    rec["failed"] = sum(f["map"] is None for f in frames)
    rec["notes"].append(f"compiles in window: {rec['compiles_in_window']}")
    rec["notes"].extend(stall_notes(frames, batches, pauses, t0))
    rec["end_to_end"] = frame_metrics(frames, t_end, seconds)
    rec["notes"].append(
        f"frames: {len(frames)} due, {len(frames) - rec['failed']} delivered, "
        f"median latency {rec['end_to_end'].pop('median_ms'):.3f} ms")


def stall_notes(frames, batches, pauses, t0: float) -> list:
    """Lines that say where a stall held the frames: the generator's
    lateness, the longest batch dispatch and delivery, the longest gap
    between batches, and the garbage collector's pauses, with times from
    the window's start."""
    notes = []
    if frames:
        late = [f["sent"] - f["due"] for f in frames]
        i = int(np.argmax(late))
        notes.append(
            f"generator lateness: max {late[i] * 1e3:.3f} ms at "
            f"{frames[i]['due'] - t0:.3f} s, p95 "
            f"{np.percentile(late, 95) * 1e3:.3f} ms over {len(late)} frames")
    if batches:
        d = max(batches, key=lambda b: b[1] - b[0])
        w = max(batches, key=lambda b: b[3] - b[2])
        gaps = [(b[0] - a[3], b[0]) for a, b in zip(batches, batches[1:])]
        g = max(gaps, default=(0.0, t0))
        notes.append(
            f"batches: {len(batches)}; longest dispatch {(d[1] - d[0]) * 1e3:.3f}"
            f" ms at {d[0] - t0:.3f} s; longest delivery "
            f"{(w[3] - w[2]) * 1e3:.3f} ms at {w[2] - t0:.3f} s; longest gap "
            f"between batches {g[0] * 1e3:.3f} ms at {g[1] - t0:.3f} s")
    by_gen = [sum(1 for p in pauses if p[0] == n) for n in range(3)]
    p = max(pauses, key=lambda p: p[2], default=(0, t0, 0.0))
    notes.append(f"gc in window: {by_gen[0]}/{by_gen[1]}/{by_gen[2]} "
                 f"collections of generation 0/1/2; longest "
                 f"{p[2] * 1e3:.3f} ms (generation {p[0]}) at {p[1] - t0:.3f} s")
    return notes


def frame_metrics(frames, t_end: float, seconds: float) -> dict:
    """Rate of maps delivered inside the window, and the 95th percentile
    (and median) over every frame due in it of delivery minus *scheduled*
    arrival; a frame never delivered counts as infinitely late."""
    done = [f["done"] for f in frames if f["done"] is not None]
    lat = [(f["done"] - f["due"]) if f["done"] is not None else float("inf")
           for f in frames]
    return {"frames_per_s": sum(d <= t_end for d in done) / seconds,
            "frame_p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "median_ms": float(np.median(lat)) * 1e3}


def check(rec: dict) -> list:
    """[(name, value, limit)]: pixels of delivered maps that differ from
    the reference map of their frame."""
    rec["service"].close()
    del rec["service"]
    taps = np.asarray(rec["config"]["kernel"])
    refs = {}
    bad = 0
    for f in rec["frames"]:
        if f["map"] is None:
            continue
        key = (f["res"], f["pool"])
        if key not in refs:
            refs[key] = ref_edge.edge_map(rec["pools"][key[0]][key[1]], taps)
        want, got = refs[key], np.asarray(f["map"])
        bad += int(want.size if got.shape != want.shape
                   else np.count_nonzero(got != want))
        f["map"] = None  # the map is judged; let it go
    return [("mismatched_pixels", bad, rec["config"]["limits"]["mismatched_pixels"])]
