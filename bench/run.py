#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic, driver and metric readers are found by name under
``bench/``. With ``--trace 0`` the result holds the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics, read from a profiler
trace of the window. ``--control`` serves the configuration's
``control_substrate`` in place of its ``substrate``: the run the limits of
``correct`` are set against. Exits non-zero, printing no result, without a
TPU of a kind in ``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


class Refused(Exception):
    """The run cannot be made here (no chip, a missing file)."""


def load_cell(name: str):
    """(bench, cell, config, traffic) for the workload ``name``."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise Refused(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, config, traffic


def device_check(chips: int):
    """The TPU devices and the peaks of their kind, or Refused."""
    if not (ROOT / "src" / "repro").is_dir():
        raise Refused("the system under test (src/repro) is not here")
    import jax

    from bench import work

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise Refused(f"{chips} chips needed, {len(devs)} found")
    from repro.kernels import blocking

    if blocking.resolve_interpret():
        raise Refused("Pallas kernels would run in interpret mode")
    try:
        peak = work.peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None
    return devs[:chips], peak


def configure_jax() -> None:
    """JAX's persistent compilation cache, at the program's fixed path (or
    ``JAX_COMPILATION_CACHE_DIR``), keeping every program it compiles so a
    cell's second run in a checkout compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def read_metric(name: str, rec: dict):
    """The per-layer metric ``name`` from its reader, or None."""
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(rec)


def run(bench, cell, config, traffic, *, seed: int, seconds: float,
        trace: bool, control: bool = False, devices=None, peak=None,
        keep_trace=None, t_start: float = T_START):
    """Set up, measure and check one cell; returns (result, checks)."""
    import numpy as np

    from bench import trace as btrace

    driver = importlib.import_module(f"bench.drivers.{config['driver']}")
    rec = {"cell": cell, "config": config, "traffic": traffic, "seed": seed,
           "seconds": seconds, "control": control, "peak": peak,
           "rng": np.random.default_rng(seed), "notes": []}
    driver.setup(rec)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        rec["setup_s"] = time.perf_counter() - t_start
        driver.measure(rec, trace_dir)
        if devices:
            stats = [d.memory_stats() or {} for d in devices]
            rec["memory_peak_bytes"] = max(s.get("peak_bytes_in_use", 0)
                                           for s in stats)
        if trace:
            tr = btrace.load(btrace.find_xplane(trace_dir))
            rec["trace"] = tr
            rec["trace_window"] = tr.window()
            if keep_trace:
                shutil.copy(btrace.find_xplane(trace_dir), keep_trace)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks = driver.check(rec)
    for note in rec["notes"]:
        print(f"[bench] {note}", file=sys.stderr, flush=True)

    metrics = {}
    if trace:
        for m in bench["per_layer"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                v = read_metric(m["name"], rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(rec["end_to_end"], setup_s=rec["setup_s"])
        for m in bench["end_to_end"]:
            if cell["name"] in m.get("workloads", [cell["name"]]) \
                    and m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    correct = rec["failed"] == 0 and all(v <= lim for _, v, lim in checks)
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics}
    if devices:
        result["device"] = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": rec["memory_peak_bytes"]}
    if trace:
        tr, (lo, hi) = rec["trace"], rec["trace_window"]
        busy = btrace.busy_ns(tr, lo, hi)
        result.setdefault("device", {}).update(
            busy_s=busy / 1e9, window_s=(hi - lo) / 1e9)
        result["breakdown"] = btrace.breakdown(tr, lo, hi)
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="serve the configuration's control_substrate")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw .xplane.pb here (with --trace 1)")
    args = ap.parse_args(argv)
    try:
        bench, cell, config, traffic = load_cell(args.workload)
        devices, peak = device_check(cell["chips"])
    except (Refused, FileNotFoundError, KeyError) as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 3
    configure_jax()
    result, checks = run(bench, cell, config, traffic, seed=args.seed,
                         seconds=args.seconds, trace=bool(args.trace),
                         control=args.control, devices=devices, peak=peak,
                         keep_trace=args.keep_trace)
    for n, v, lim in checks:
        print(f"[bench] check {n}: {v!r} (limit {lim!r})", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
