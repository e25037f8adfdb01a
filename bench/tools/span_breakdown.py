"""Where a cell's device waits, by the program's own spans.

    python3 bench/tools/span_breakdown.py --workload vit16.flow_eval \\
        --seed 11 --seconds 30 --fixture trace_program.json

Runs set-up and one traced window of the cell as ``bench/run.py --trace
1`` does (without the checks; a fresh process, so the window follows
the same warm-up), and prints one JSON line: designs per second, the
harness's readings (device idle share, ``driver_host_share.search``),
those of ``bench.harness.program`` (driver preparation and engine
transfer shares, the flow netsim's useful lane share and its fill-loop
bound), the idle time by innermost span, and the ten longest idle gaps
named by program spans. The cost of tracing is the designs per second
here against ``bench/run.py --trace 0`` with the same seed.
``--fixture`` adds the start of the window, with its program spans, to
a JSON file of test fixtures under the cell's name.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

from common import ROOT, start


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--fixture")
    ap.add_argument("--events", type=int, default=300)
    args = ap.parse_args()
    start()
    import jax

    from bench.harness import drive, manifest, program, sut, trace

    cell = manifest.cell(ROOT, args.workload)
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
        tr = program.reduce_xplane(held["xplane"])
    win = trace.window_ns(tr)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "designs_per_s": w.designs / w.seconds, "calls": w.attempted,
        "compiles": w.compiles, "window_s": win / 1e9,
        "busy_s": trace.busy_ns(tr) / 1e9,
        "device_idle_share": 100.0 * trace.idle_share(tr),
        "driver_host_share": 100.0 * trace.idle_under(tr, "sweep_call")
        / win,
        "driver_prep_share": program.prep_share(tr),
        "engine_transfer_share": program.transfer_share(tr),
        "flow_lane_useful_share": program.lane_share(tr, "events"),
        "flow_fill_share_bound": program.lane_share(tr, "fills"),
        "idle_by_span_s": program.idle_by_span(tr),
        "idle_gaps": program.idle_gaps(tr),
        "program_spans": len(tr["program"])}), flush=True)
    if args.fixture:
        pieces = {}
        if os.path.exists(args.fixture):
            with open(args.fixture) as f:
                pieces = json.load(f)
        pieces[args.workload] = program.small(tr, args.events)
        with open(args.fixture, "w") as f:
            json.dump(pieces, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
