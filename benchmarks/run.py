"""Run every benchmark (one per paper table/figure + the roofline table).

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # full (slow)
    PYTHONPATH=src python -m benchmarks.run --fast     # reduced sweep
    PYTHONPATH=src python -m benchmarks.run --backend numpy   # reference

All figure scripts drive their grids through :mod:`repro.core.sweep`:
LS baselines are evaluated in batched compiled calls and cached
process-wide, so figures sharing workloads (fig8/fig9/fig12) never
re-evaluate a baseline. ``--backend`` picks the evaluator engine
(DESIGN.md §8); numpy is the bit-identical reference path.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full workload sweep (~60+ min); default is the "
                         "bounded profile — the full-sweep outputs are "
                         "archived in benchmarks/artifacts/")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset (fig3,fig8,fig9_10,"
                         "fig11,fig12,fig13,fig_hetero,roofline)")
    ap.add_argument("--backend", default="jax", choices=("numpy", "jax"),
                    help="execution backend for baselines + GA fitness "
                         "+ the fig3 netsim sweep (DESIGN.md §8/§11); "
                         "backends agree to float64 round-off (rtol "
                         "1e-9), jax is faster on large sweeps")
    args = ap.parse_args()

    args.fast = not args.full
    be = args.backend
    from repro.core import sweep
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()

    from . import (fig3_motivation, fig8_latency_hbm, fig9_10_scaling,
                   fig11_pipelining, fig12_lowbw, fig13_ablation,
                   fig_hetero, roofline)

    benches = {
        "fig3": lambda: fig3_motivation.main(backend=be),
        "fig8": lambda: fig8_latency_hbm.main(fast=args.fast, backend=be),
        "fig9_10": lambda: fig9_10_scaling.main(fast=args.fast, backend=be),
        "fig11": lambda: fig11_pipelining.main(fast=args.fast, backend=be),
        "fig12": lambda: fig12_lowbw.main(fast=args.fast, backend=be),
        "fig13": lambda: fig13_ablation.main(fast=args.fast, backend=be),
        "fig_hetero": lambda: fig_hetero.main(fast=args.fast, backend=be),
        "roofline": lambda: roofline.main(),
    }
    only = args.only.split(",") if args.only else list(benches)
    failed = []
    prev = sweep.cache_stats()
    for name in only:
        print(f"# ===== {name} =====")
        try:
            benches[name]()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        # Per-figure cache effectiveness: hits/misses this figure added
        # on top of the process-wide sweep cache (eval + solver records).
        cur = sweep.cache_stats()
        print(f"# {name}: sweep cache +{cur['hits'] - prev['hits']} hits "
              f"/ +{cur['misses'] - prev['misses']} misses")
        prev = cur
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)
    total = sweep.cache_stats()
    print(f"# sweep cache totals: {total['hits']} hits / "
          f"{total['misses']} misses")
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
