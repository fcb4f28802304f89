#!/usr/bin/env python3
"""Find an open-loop edge cell's knee: serve its traffic at several rates in
one process (one set-up, one compile) and print, per rate, what was offered,
what was delivered inside the window, the latency quantiles and the backlog
left at the window's end.

    python bench/sweep.py --workload edge-1080p.steady --rates 16,20,24 \
        --seconds 10 --seed 1

The knee is the highest rate whose delivered rate keeps up with the offered
one and whose backlog does not grow; a traffic file states 0.8 of it.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys
import time

import run as brun  # bench/run.py, beside this file


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, in the traffic "
                         "file's rate unit")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    t_start = time.perf_counter()
    bench, cell, config, traffic = brun.load_cell(args.workload)
    devices, peak = brun.device_check(cell["chips"])
    brun.configure_jax()
    import numpy as np

    from bench.drivers import edge

    key = "rate_per_s"
    rec = {"cell": cell, "config": config, "traffic": dict(traffic),
           "seed": args.seed, "seconds": args.seconds, "control": False,
           "peak": peak, "rng": np.random.default_rng(args.seed), "notes": []}
    edge.setup(rec)
    print(f"[sweep] set-up {time.perf_counter() - t_start:.1f} s",
          file=sys.stderr, flush=True)
    arrivals = importlib.import_module(f"bench.arrivals.{traffic['kind']}")
    for rate in (float(r) for r in args.rates.split(",")):
        rec["traffic"][key] = rate
        rec["schedule"] = arrivals.schedule(rec["traffic"], args.seconds,
                                            rec["rng"])
        rec["service"].metrics.reset()
        rec["notes"] = []
        edge.measure(rec, None)
        t_end = rec["t_end"]
        done = [f["done"] for f in rec["frames"] if f["done"] is not None]
        lat = np.array([f["done"] - f["due"] for f in rec["frames"]
                        if f["done"] is not None])
        print(json.dumps({
            "rate": rate, "offered_frames_per_s": len(rec["frames"]) / args.seconds,
            "frames_per_s": rec["end_to_end"]["frames_per_s"],
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": rec["end_to_end"]["frame_p95_ms"],
            "max_ms": float(lat.max()) * 1e3,
            "backlog_at_end": sum(d > t_end for d in done),
            "batch_fill": rec["service"].metrics.mean_occupancy()}), flush=True)
        for f in rec["frames"]:
            f["map"] = None
    rec["service"].close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
