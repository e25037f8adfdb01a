"""Fig. 9/10 reproduction: latency and EDP scaling on type-A systems of
4×4 / 8×8 / 16×16 chiplets.

Paper claims: MIQP geo-mean 55.5% (latency) / 60.3% (EDP) over LS; GA
24.2% / 35.1%. MIQP > GA, with AlexNet gaining more on larger systems
(redistribution savings grow with scale); GA is relatively stronger on
EDP than latency.

Grid driving (benchmarks/README.md): the (grid × workload) LS references
are one batched sweep (latency and EDP come out of the same records);
the (objective × grid × workload) GA searches run island-batched through
``sweep.solve_grid`` (one compiled call per shape group, DESIGN.md §10);
the MIQP grid runs batched lattice solves through
``sweep.solve_grid(method="miqp")`` (DESIGN.md §12) followed by the
per-point side-variable polish of ``optimize(method="miqp")``; both
solvers' final schedules are scored by batched ``eval_sweep`` calls.
"""
from __future__ import annotations

import time

from repro.core import (EvalOptions, make_hw, refine_schedule, sweep)
from repro.core.ga import GAConfig
from repro.core.miqp import MIQPConfig
from repro.graphs import PAPER_WORKLOADS, WORKLOADS

from .common import emit, geomean, save_json

GA_CFG = GAConfig(generations=60, population=64)
MIQP_CFG = MIQPConfig(time_limit=60, edp_sweep=3)
GA_OPTS = EvalOptions(redistribution=True, async_exec=True)
# Sec. 6.3 solves under the sync approximation (no async fusion); the
# result is then polished + scored under the full GA_OPTS runtime —
# the same split optimize(method="miqp") applies.
MIQP_SOLVE_OPTS = EvalOptions(redistribution=True, async_exec=False)


def main(fast: bool = False, backend: str = "jax"):
    grids = (4, 8) if fast else (4, 8, 16)
    wnames = ("alexnet", "hydranet") if fast else PAPER_WORKLOADS
    tasks = {w: WORKLOADS[w](batch=1) for w in wnames}
    hws = {g: make_hw("A", g, "hbm") for g in grids}

    base_grid = sweep.grid(g=grids, wname=wnames)
    base_recs = sweep.eval_sweep(
        [sweep.EvalPoint(tasks[p["wname"]], hws[p["g"]])
         for p in base_grid],
        backend=backend)
    ref = {(p["g"], p["wname"]): r for p, r in zip(base_grid, base_recs)}

    results = {}
    sp_all = {(o, m): [] for o in ("latency", "edp") for m in ("ga", "miqp")}

    # ---- GA: island-batched solves + one batched scoring sweep per
    # objective (same diagonal-link/options setup as optimize(method="ga")).
    for o in ("latency", "edp"):
        fig = "fig9" if o == "latency" else "fig10"
        pts = [sweep.EvalPoint(tasks[p["wname"]],
                               hws[p["g"]].replace(diagonal_links=True),
                               GA_OPTS)
               for p in base_grid]
        t0 = time.perf_counter()
        ga_recs = sweep.solve_grid(pts, o, GA_CFG, backend=backend)
        us = (time.perf_counter() - t0) * 1e6
        score = sweep.eval_sweep(
            [sweep.EvalPoint(pt.task, pt.hw, GA_OPTS,
                             partition=r.partition,
                             redist_mask=r.redist_mask)
             for pt, r in zip(pts, ga_recs)],
            backend=backend)
        # solve time is per batched call (compile included on a cold
        # cache), not per point — emitted once; per-point rows carry the
        # speedups.
        emit(f"{fig}/ga/solve_grid_total", us, f"{len(pts)} points")
        for p, rec in zip(base_grid, score):
            g, wname = p["g"], p["wname"]
            sp = ref[(g, wname)][o] / rec[o]
            sp_all[(o, "ga")].append(sp)
            results[f"{fig}/{g}/{wname}/ga"] = sp
            emit(f"{fig}/{g}x{g}/{wname}/ga", 0.0, f"speedup={sp:.3f}x")

    # ---- MIQP: batched lattice solves per objective (DESIGN.md §12),
    # then the cheap per-point polish and one batched scoring sweep —
    # the optimize(method="miqp") pipeline, grid-vectorized.
    for o in ("latency", "edp"):
        fig = "fig9" if o == "latency" else "fig10"
        pts = [sweep.EvalPoint(tasks[p["wname"]],
                               hws[p["g"]].replace(diagonal_links=True),
                               MIQP_SOLVE_OPTS)
               for p in base_grid]
        t0 = time.perf_counter()
        mi_recs = sweep.solve_grid(pts, o, MIQP_CFG, backend=backend,
                                   method="miqp")
        us = (time.perf_counter() - t0) * 1e6
        emit(f"{fig}/miqp/solve_grid_total", us, f"{len(pts)} points")
        polished = [refine_schedule(pt.task, pt.hw, GA_OPTS, r.partition,
                                    r.redist_mask, o, backend=backend)
                    for pt, r in zip(pts, mi_recs)]
        score = sweep.eval_sweep(
            [sweep.EvalPoint(pt.task, pt.hw, GA_OPTS, partition=part,
                             redist_mask=rd)
             for pt, (part, rd) in zip(pts, polished)],
            backend=backend)
        for p, rec in zip(base_grid, score):
            g, wname = p["g"], p["wname"]
            sp = ref[(g, wname)][o] / rec[o]
            sp_all[(o, "miqp")].append(sp)
            results[f"{fig}/{g}/{wname}/miqp"] = sp
            emit(f"{fig}/{g}x{g}/{wname}/miqp", 0.0, f"speedup={sp:.3f}x")

    for o in ("latency", "edp"):
        fig = "fig9" if o == "latency" else "fig10"
        for m in ("ga", "miqp"):
            emit(f"{fig}/geomean/{m}", 0.0,
                 f"{(geomean(sp_all[(o, m)]) - 1) * 100:+.1f}% vs LS "
                 f"(paper: GA +24.2/35.1%, MIQP +55.5/60.3%)")
    save_json("fig9_10", results)


if __name__ == "__main__":
    main()
