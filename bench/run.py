"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` at the root of the checkout. The run needs a
TPU with at least as many chips as the cell asks for, and exits nonzero
without a result otherwise. Set-up builds the system, warms every
executable the traffic reaches (from the persistent compile cache after
the first run) and starts from an empty sweep cache; then one window of
``--seconds`` is measured. After the window every answer (or a sample
drawn from the seed) is compared with the plain reference. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared with its limit.
With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def finite(x: float) -> float:
    """JSON has no infinity: an unbounded reading prints as the largest
    float."""
    return x if math.isfinite(x) else sys.float_info.max


def main(argv=None) -> int:
    from bench.harness import manifest

    args = parse(argv)
    cell = manifest.cell(ROOT, args.workload)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"bench: needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(out), flush=True)
    return 0


def run_cell(cell, seed: int, seconds: float, traced: bool, devs,
             timed_patch=None) -> dict:
    """Set-up, one window, the checks and the readings of one run; returns
    the result line as a dict. ``devs`` are the devices JAX reports.
    ``timed_patch`` (tests only) is a context manager held for exactly
    the measured window, to break the timed path underneath."""
    import jax

    from bench.harness import check, drive, manifest, sut, trace

    clock = drive.CompileClock()
    system = sut.System(cell.config, devices=cell.devices)
    tmp = tempfile.TemporaryDirectory() if traced else None
    opened = {}

    def on_open():
        if timed_patch is not None:
            timed_patch.__enter__()
        if tmp is not None:
            trace.capture_start(tmp.name)
            opened["span"] = jax.profiler.TraceAnnotation(
                trace.PREFIX + "window")
            opened["span"].__enter__()
        opened["setup_s"] = time.perf_counter() - T_START
        opened["compile_s"] = clock.seconds

    def on_close():
        if tmp is not None:
            opened["span"].__exit__(None, None, None)
            opened["xplane"] = trace.capture_stop(tmp.name)
        if timed_patch is not None:
            timed_patch.__exit__(None, None, None)

    w = cell.driver.drive(system, cell.kind, cell.traffic, cell.config,
                          seed, seconds, clock, on_open, on_close)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    tr = trace.reduce_xplane(opened["xplane"]) if tmp is not None else None
    if tmp is not None:
        tmp.cleanup()
    system.sweep.clear_cache()

    print(f"window: {w.seconds!r} s, {w.attempted} attempted, {w.failed} "
          f"failed, {w.designs} designs, {w.compiles} compiles inside; "
          f"set-up {opened['setup_s']!r} s of which compile "
          f"{opened['compile_s']!r} s", file=sys.stderr)

    checks = check.Checks(cell.traffic["limits"])
    checks.count("calls_failed", 0)
    for _ in range(w.failed):
        checks.count("calls_failed", True)
    cell.kind.check(checks, check.Reference(cell.config, cell.root),
                    cell.traffic, w, seed)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    ctx = {"window": w, "setup_s": opened["setup_s"], "trace": tr,
           "chips": cell.chips}
    metrics, breakdown = {}, None
    for spec in cell.per_layer if traced else cell.end_to_end:
        v = manifest.reader(cell.root, spec["name"])(ctx)
        if v is not None:
            metrics[spec["name"]] = {"value": finite(v), "unit": spec["unit"]}
    if tr is not None:
        n, first, last = trace.coverage(tr)
        print(f"trace: {n} device operations in a "
              f"{trace.window_ns(tr) / 1e9!r} s window, the first starting "
              f"{first!r} s and the last ending {last!r} s after it opened",
              file=sys.stderr)
        device["busy_s"] = trace.busy_ns(tr) / 1e9
        device["window_s"] = trace.window_ns(tr) / 1e9
        breakdown = {"device_ops": trace.top_ops(tr),
                     "idle_gaps": trace.idle_gaps(tr)}
    for line in checks.lines():
        print(line, file=sys.stderr)
    out = {"correct": checks.correct,
           "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                     for k, v in checks.report().items()}
    return out


if __name__ == "__main__":
    sys.exit(main())
