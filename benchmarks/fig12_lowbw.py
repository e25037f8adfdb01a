"""Fig. 12 reproduction: low-bandwidth (DRAM) 4×4 type-A systems.

Paper claims: GA/MIQP latency speedups of 40%/72% over LS (EDP 28%/37%),
with the GA–MIQP gap *wider* than the HBM case (off-chip congestion
simplifies the on-chip scheduling space, so MIQP solves closer to
optimal within its budget).

Grid driving (benchmarks/README.md): per-workload LS references come
from one batched sweep, then get the same batch-4 pipelining treatment
as the solver rows (the co-search scores pipelined makespans, so the LS
side must too — see benchmarks/README.md for this semantic change); the
old per-(objective × workload) GA grid is replaced by ONE batched
Pareto-front ``sweep.cosearch_sweep`` (DESIGN.md §16) whose front
serves BOTH objective readings per workload from a single search; the
MIQP grid runs batched lattice solves through
``sweep.solve_grid(method="miqp")`` (DESIGN.md §12) plus the per-point
polish and one batched scoring sweep per objective.
"""
from __future__ import annotations

import time

from repro.core import (CoSearchConfig, EvalOptions, make_hw,
                        refine_schedule, sweep)
from repro.core.miqp import MIQPConfig
from repro.core.sweep import PipelinePoint
from repro.graphs import PAPER_WORKLOADS, WORKLOADS

from .common import emit, geomean, save_json

# same budget envelope as the old per-pass GA_CFG
# (GAConfig(generations=60, population=64)); batch matches the
# pipelined references below.
CO_CFG = CoSearchConfig(generations=60, population=64, batch=4)
MIQP_CFG = MIQPConfig(time_limit=60, edp_sweep=3)
MIQP_OPTS = EvalOptions(redistribution=True, async_exec=True)
MIQP_SOLVE_OPTS = EvalOptions(redistribution=True, async_exec=False)
BATCH = 4


def main(fast: bool = False, backend: str = "jax"):
    hw = make_hw("A", 4, "dram")
    wnames = ("alexnet", "hydranet") if fast else PAPER_WORKLOADS
    tasks = {w: WORKLOADS[w](batch=1) for w in wnames}
    opts = EvalOptions(redistribution=True, async_exec=True)

    # LS references, pipelined at the co-search's batch: the LS
    # partition's per-op segments through one batched pipeline_sweep,
    # latency = pipelined makespan / batch, EDP = energy × that.
    base_recs = sweep.eval_sweep(
        [sweep.EvalPoint(tasks[w], hw) for w in wnames], backend=backend)
    base_pipes = sweep.pipeline_sweep(
        [PipelinePoint(
            [(f"op{i}", float(r["t_in"][i]), float(r["t_comp"][i]),
              float(r["t_out"][i])) for i in range(len(tasks[w]))],
            BATCH)
         for w, r in zip(wnames, base_recs)],
        backend=backend)
    ref = {}
    for w, r, p in zip(wnames, base_recs, base_pipes):
        lat = p.pipelined / BATCH
        ref[w] = {"latency": lat, "edp": r["energy"] * lat}

    results = {}
    sp = {(o, m): [] for o in ("latency", "edp")
          for m in ("ga", "miqp")}

    # ---- fused co-search (DESIGN.md §16): ONE batched call; the
    # Pareto front's min-EDP and min-latency rows serve both objective
    # readings (the old flow ran a separate GA pass per objective).
    t0 = time.perf_counter()
    co_recs = sweep.cosearch_sweep(
        [sweep.EvalPoint(tasks[w], hw, opts) for w in wnames],
        "edp", CO_CFG, backend=backend)
    us = (time.perf_counter() - t0) * 1e6
    emit("fig12/cosearch/sweep_total", us, f"{len(wnames)} points")
    for w, r in zip(wnames, co_recs):
        for o, val in (("latency", float(r.front["latency"].min())),
                       ("edp", r.edp)):
            s = ref[w][o] / val
            sp[(o, "ga")].append(s)
            results[f"{o}/{w}/ga"] = s
            emit(f"fig12/{o}/{w}/cosearch", 0.0, f"speedup={s:.3f}x")

    # MIQP: batched lattice solves + polish + batched scoring
    # (DESIGN.md §12) — the optimize(method="miqp") pipeline.
    hw_diag = hw.replace(diagonal_links=True)
    for o in ("latency", "edp"):
        pts = [sweep.EvalPoint(tasks[w], hw_diag, MIQP_SOLVE_OPTS)
               for w in wnames]
        t0 = time.perf_counter()
        mi_recs = sweep.solve_grid(pts, o, MIQP_CFG, backend=backend,
                                   method="miqp")
        us = (time.perf_counter() - t0) * 1e6
        emit(f"fig12/{o}/miqp/solve_grid_total", us, f"{len(pts)} points")
        polished = [refine_schedule(pt.task, pt.hw, MIQP_OPTS, r.partition,
                                    r.redist_mask, o, backend=backend)
                    for pt, r in zip(pts, mi_recs)]
        score = sweep.eval_sweep(
            [sweep.EvalPoint(pt.task, pt.hw, MIQP_OPTS, partition=part,
                             redist_mask=rd)
             for pt, (part, rd) in zip(pts, polished)],
            backend=backend)
        # same batch-4 pipelining treatment as the LS references and
        # the co-search rows — one batched pipeline_sweep per objective.
        mi_pipes = sweep.pipeline_sweep(
            [PipelinePoint(
                [(f"op{i}", float(rec["t_in"][i]),
                  float(rec["t_comp"][i]), float(rec["t_out"][i]))
                 for i in range(len(tasks[w]))], BATCH)
             for w, rec in zip(wnames, score)],
            backend=backend)
        for wname, rec, p in zip(wnames, score, mi_pipes):
            lat = p.pipelined / BATCH
            val = lat if o == "latency" else rec["energy"] * lat
            s = ref[wname][o] / val
            sp[(o, "miqp")].append(s)
            results[f"{o}/{wname}/miqp"] = s
            emit(f"fig12/{o}/{wname}/miqp", 0.0, f"speedup={s:.3f}x")

    for o in ("latency", "edp"):
        for m in ("ga", "miqp"):
            emit(f"fig12/{o}/geomean/{m}", 0.0,
                 f"{(geomean(sp[(o, m)]) - 1) * 100:+.1f}% vs LS")
    save_json("fig12", results)


if __name__ == "__main__":
    main()
