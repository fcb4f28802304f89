#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: one process runs a
cell's program on many seeds, its control (``control_substrate``) on a
few, and optionally the program with a planted fault (``bench/faults.py``)
on a few, each with a short window at the cell's own load, and prints one
JSON line per run with every number compared.

    python bench/calibrate.py --workload edge-1080p.steady --seconds 10 \
        --seeds 101,102,103 --control-seeds 201,202,203
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import run as brun  # bench/run.py, beside this file
from bench.faults import FAULTS


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None,
                    help="a fault of bench/faults.py, planted for --fault-seeds")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    bench, cell, config, traffic = brun.load_cell(args.workload)
    devices, peak = brun.device_check(cell["chips"])
    brun.configure_jax()
    runs = [(int(s), False, None) for s in args.seeds.split(",") if s] + \
           [(int(s), True, None) for s in args.control_seeds.split(",") if s] + \
           [(int(s), False, args.fault) for s in args.fault_seeds.split(",") if s]
    for seed, control, fault in runs:
        t = time.perf_counter()
        try:
            with (FAULTS[fault]() if fault else contextlib.nullcontext()):
                result, _ = brun.run(bench, cell, config, traffic, seed=seed,
                                     seconds=args.seconds, trace=False,
                                     control=control, devices=devices,
                                     peak=peak, t_start=t)
            line = {k: result[k] for k in ("correct", "attempted", "failed",
                                           "checks")}
        except Exception as e:  # noqa: BLE001 - a control may crash
            line = {"error": repr(e)}
        print(json.dumps({"seed": seed, "control": control, "fault": fault,
                          "seconds": time.perf_counter() - t, **line}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
