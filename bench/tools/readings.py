"""Readings of the numbers that decide ``correct``, over many seeds in one
process: the program as it is (``--mode program``, the lower readings),
the control (``--mode control``: every engine outside its float64
scope, the upper readings), or the program with one of
``bench.harness.faults.FAULTS`` planted in the window
(``--mode no_search``, say).

    python3 bench/tools/readings.py --workload vit16.ga_islands \\
        --mode control --seeds 11,12,13 --seconds 10
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import warnings

from common import ROOT, seeds, start


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", default="program")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    devs = start()
    from bench import run
    from bench.harness import faults, manifest

    cell = manifest.cell(ROOT, args.workload)
    ctx = faults.float32() if args.mode == "control" \
        else contextlib.nullcontext()
    fault = faults.FAULTS.get(args.mode)
    warnings.simplefilter("ignore")
    with ctx:
        for s in seeds(args.seeds):
            out = run.run_cell(cell, s, args.seconds, False, devs,
                               timed_patch=fault() if fault else None)
            print(json.dumps({"workload": args.workload, "mode": args.mode,
                              "seed": s, "correct": out["correct"],
                              "attempted": out["attempted"],
                              "metrics": out["metrics"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
