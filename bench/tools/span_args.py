"""The arguments of the program's own spans in one traced window of a cell.

    python3 bench/tools/span_args.py --workload dsv2.ga_islands --seed 11 \\
        --seconds 30

Runs set-up and one traced window of the cell as ``bench/run.py --trace
1`` does (without the checks), reads the program's spans with
``bench.harness.program.reduce_spans`` and prints one JSON line: for each
span name asked for (``--spans``, comma-separated), the arguments of
every such span in the window, and their sums. For a configuration with
routed experts, ``ga.consts`` and ``sweep.consts`` carry ``grouped_ops``
and ``groups``, and ``ga.results`` carries ``expert_fetch_excess``: over
each call's best genomes, the expert weight fetches that rows
straddling chiplet rows add.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile

from common import ROOT, start


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--spans", default="ga.consts,ga.results,sweep.consts")
    args = ap.parse_args()
    devs = start()
    import jax

    from bench.harness import drive, manifest, program, sut, trace

    cell = manifest.cell(ROOT, args.workload)
    if len(devs) < cell.chips:
        sys.exit(f"needs {cell.chips} TPU chip(s); found {len(devs)}")
    clock = drive.CompileClock()
    system = sut.System(cell.config, devices=cell.devices)
    held = {}

    def on_open():
        trace.capture_start(held["dir"])
        held["span"] = jax.profiler.TraceAnnotation(trace.PREFIX + "window")
        held["span"].__enter__()

    def on_close():
        held["span"].__exit__(None, None, None)
        held["xplane"] = trace.capture_stop(held["dir"])

    with tempfile.TemporaryDirectory() as tmp:
        held["dir"] = tmp
        w = cell.driver.drive(system, cell.kind, cell.traffic, cell.config,
                              args.seed, args.seconds, clock, on_open,
                              on_close)
        spans = program.reduce_spans(held["xplane"])
    out = {"workload": args.workload, "seed": args.seed,
           "calls": w.attempted, "spans": {}}
    for name in args.spans.split(","):
        seen = [a for n, _, _, a in spans if n == name]
        totals = {}
        for a in seen:
            for k, v in a.items():
                if isinstance(v, (int, float)):
                    totals[k] = totals.get(k, 0) + v
        out["spans"][name] = {"count": len(seen), "sums": totals,
                              "first": seen[0] if seen else None}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
