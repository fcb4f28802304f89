#!/usr/bin/env python3
"""``bench/calibrate.py`` for the LM cells: the faults of
``bench/faults_lm.py`` can be planted too, several in one process (one
set-up of the chip and one compile cache for all of them).

    python bench/calibrate_lm.py --workload lm-decode.approx --seconds 10 \
        --seeds 101,102 --control-seeds 201,202,203 \
        --faults altered_logit,stale_cache --fault-seeds 301,302,303
"""
from __future__ import annotations

import argparse
import sys

import calibrate  # bench/calibrate.py, beside this file
from bench.faults_lm import FAULTS


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", default="")
    args = ap.parse_args()
    calibrate.FAULTS = {**calibrate.FAULTS, **FAULTS}
    base = ["--workload", args.workload, "--seconds", args.seconds]
    runs = [base + ["--seeds", args.seeds, "--control-seeds",
                    args.control_seeds]]
    runs += [base + ["--fault", f, "--fault-seeds", args.fault_seeds]
             for f in args.faults.split(",") if f]
    for argv in runs:
        sys.argv = [calibrate.__file__, *argv]
        calibrate.main()
    return 0


if __name__ == "__main__":
    sys.exit(main())
