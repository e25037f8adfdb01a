"""Record a short profiler trace of one cell's calls and save its start,
in the plain-event form of ``bench.harness.trace``, as a test fixture.

    python3 bench/tools/record_trace.py --workload vit16.ga_islands \\
        --calls 2 --out trace_small.json
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
    ap.add_argument("--calls", type=int, default=2)
    ap.add_argument("--events", type=int, default=300)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    start()
    import jax

    from bench.harness import manifest, sut, trace

    cell = manifest.cell(ROOT, args.workload)
    kind = cell.kind
    system = sut.System(cell.config, devices=cell.devices)
    calls = [kind.call(cell.traffic, cell.config, 1, i)
             for i in range(args.calls)]
    for c in calls:
        kind.run(system, c, kind.prepare(system, c), cache=False)
    with tempfile.TemporaryDirectory() as tmp:
        trace.capture_start(tmp)
        with jax.profiler.TraceAnnotation(trace.PREFIX + "window"):
            for c in calls:
                with trace.span("generate"):
                    args_ = kind.prepare(system, c)
                with trace.span("sweep_call"):
                    kind.run(system, c, args_, cache=False)
        tr = trace.reduce_xplane(trace.capture_stop(tmp))
    small = trace.small(tr, args.events)
    with open(args.out, "w") as f:
        json.dump(small, f)
    print(json.dumps({"devices": list(tr["devices"]),
                      "ops": {d: len(v["ops"]) for d, v in tr["devices"].items()},
                      "modules": {d: sorted({m[0] for m in v["modules"]})[:20]
                                  for d, v in tr["devices"].items()},
                      "spans": sorted({s[0] for s in tr["spans"]}),
                      "window": tr["window"],
                      "busy_s": trace.busy_ns(tr) / 1e9,
                      "top_ops": trace.top_ops(tr),
                      "idle_gaps": trace.idle_gaps(tr)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
