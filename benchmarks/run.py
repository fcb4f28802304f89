"""Benchmark harness — one module per paper table/figure.

Prints a ``name,us_per_call,derived`` CSV at the end (plus human-readable
tables as it goes). ``python -m benchmarks.run [--only table4]
[--substrates exact,approx_pallas] [--sharded]`` — the substrate-sweep
benches (fig9, kernel) default to every substrate registered in
``repro.nn.substrate``; ``--sharded`` adds the kernel bench's
``dot_general`` + ``Partitioning`` rows (sweeps sharded contractions over a
mesh of every visible device — the TPU-native run's sharded sweep).

Machine-readable artifacts: the ``kernel`` bench writes
``BENCH_kernels.json``, the ``serve_edge`` bench writes
``BENCH_serving.json`` (throughput/latency records + the substrate-meter
energy rollup), and the ``autotune`` bench writes ``BENCH_autotune.json``
(plan-vs-uniform PDP/PSNR table; ``--plan`` evaluates a saved plan/bundle
instead of searching), and the ``qat`` bench writes ``BENCH_qat.json``
(pre/post-QAT quality across wirings × widths + recovered operating
points) at the repo root, so one ``python -m benchmarks.run`` produces the
full perf trajectory. Trace files are opt-in via each bench's
standalone ``--trace`` flag.
"""
from __future__ import annotations

import argparse
import sys
import traceback

from benchmarks import (
    autotune_plan,
    edge_serving,
    fig9_edge,
    fig10_tradeoff,
    kernelbench,
    qat_recovery,
    table2_compressors,
    table3_compressor4,
    table4_errors,
    table5_hardware,
)
from repro.launch.compile_cache import enable_compile_cache

MODULES = {
    "table2": table2_compressors,
    "table3": table3_compressor4,
    "table4": table4_errors,
    "table5": table5_hardware,
    "fig9": fig9_edge,
    "fig10": fig10_tradeoff,
    "kernel": kernelbench,
    "serve_edge": edge_serving,
    "autotune": autotune_plan,
    "qat": qat_recovery,
}


# benches that sweep the ProductSubstrate registry (accept substrates=[...])
_SUBSTRATE_SWEEPS = ("fig9", "kernel", "serve_edge")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(MODULES))
    ap.add_argument("--substrates", default=None,
                    help="CSV of substrate specs for the sweep benches "
                         "(default: all registered)")
    ap.add_argument("--sharded", action="store_true",
                    help="add the kernel bench's sharded dot_general rows "
                         "(Partitioning over a mesh of all visible devices)")
    ap.add_argument("--plan", default=None, metavar="PATH",
                    help="substrate-plan JSON or bundle dir for the "
                         "autotune bench (default: greedy search)")
    args = ap.parse_args()
    enable_compile_cache()
    substrates = args.substrates.split(",") if args.substrates else None

    rows = []
    failed = False
    for name, mod in MODULES.items():
        if args.only and name != args.only:
            continue
        kwargs = {"substrates": substrates} if name in _SUBSTRATE_SWEEPS else {}
        if name == "kernel":
            kwargs["sharded"] = args.sharded
        if name == "autotune":
            kwargs["plan"] = args.plan
        try:
            rows.extend(mod.run(**kwargs))
        except Exception:  # noqa: BLE001
            failed = True
            print(f"[bench {name}] FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
