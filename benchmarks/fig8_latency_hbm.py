"""Fig. 8 reproduction: normalized end-to-end latency of SIMBA-like / GA /
MIQP vs the LS-uniform baseline, on 4×4 chiplet systems of all four
packaging types with HBM.

Paper claims: GA/MIQP beat LS on every type (geo-means 13%/45%, 5%/15%,
9%/43%, 19%/25% for A–D); SIMBA-like is slightly *worse* than LS; the
GA–MIQP gap is smallest on type D (near-uniform memory distance).

Grid driving (benchmarks/README.md): LS baselines for the whole
(type × workload) grid come from the batched sweep engine — one compiled
call per shape group, cached process-wide; the solver points go through
the per-point ``sweep.run_grid``/``optimize`` path — every packaging
type is its own shape signature here, so there is nothing to batch
within a (type, workload) cell, though ``optimize(method="miqp")`` now
solves each point with the lattice engine (DESIGN.md §12).
"""
from __future__ import annotations

from repro.core import EvalOptions, make_hw, optimize, sweep
from repro.core.ga import GAConfig
from repro.core.miqp import MIQPConfig
from repro.graphs import PAPER_WORKLOADS, WORKLOADS

from .common import emit, geomean, save_json

GA_CFG = GAConfig(generations=60, population=64)          # ~paper budget
MIQP_CFG = MIQPConfig(time_limit=60)
METHOD_KW = {"simba": {},
             "ga": {"ga_config": GA_CFG},
             "miqp": {"miqp_config": MIQP_CFG}}


def main(fast: bool = False, backend: str = "jax"):
    workloads = {k: WORKLOADS[k](batch=1) for k in PAPER_WORKLOADS}
    if fast:
        workloads = {k: workloads[k] for k in ("alexnet", "hydranet")}
    hws = {t: make_hw(t, 4, "hbm") for t in "ABCD"}

    # LS baselines: one batched + cached sweep over the full
    # (type × workload × congestion-model) grid. The congestion axis
    # (DESIGN.md §11) scores the same schedules against the flow-level
    # netsim; the regime records anchor the speedup columns below, the
    # flow/regime ratio is reported as a model-fidelity diagnostic.
    base_grid = sweep.grid(t=list(hws), wname=list(workloads),
                           congestion=("regime", "flow"))
    base_recs = sweep.eval_sweep(
        [sweep.EvalPoint(workloads[p["wname"]], hws[p["t"]],
                         EvalOptions(congestion=p["congestion"]))
         for p in base_grid],
        backend=backend)
    base = {(p["t"], p["wname"]): r["latency"]
            for p, r in zip(base_grid, base_recs)
            if p["congestion"] == "regime"}
    flow = {(p["t"], p["wname"]): r["latency"]
            for p, r in zip(base_grid, base_recs)
            if p["congestion"] == "flow"}

    results = {}
    for (t, wname), lat in flow.items():
        ratio = lat / base[(t, wname)]
        results[f"{t}/{wname}/flow_vs_regime"] = ratio
        emit(f"fig8/{t}/{wname}/flow_vs_regime", 0.0, f"{ratio:.3f}x")
    speed = {(t, m): [] for t in hws for m in METHOD_KW}

    def solve(t, wname, method):
        return optimize(workloads[wname], hws[t], method, "latency",
                        backend=backend, **METHOD_KW[method])

    def report(pt, r, us):
        t, wname, method = pt["t"], pt["wname"], pt["method"]
        sp = base[(t, wname)] / r.latency
        speed[(t, method)].append(sp)
        results[f"{t}/{wname}/{method}"] = sp
        emit(f"fig8/{t}/{wname}/{method}", us, f"speedup={sp:.3f}x")

    sweep.run_grid(
        sweep.grid(t=list(hws), wname=list(workloads),
                   method=list(METHOD_KW)),
        solve, emit=report)

    for t in hws:
        for m in METHOD_KW:
            emit(f"fig8/{t}/geomean/{m}", 0.0,
                 f"{(geomean(speed[(t, m)]) - 1) * 100:+.1f}% vs LS")
    save_json("fig8", results)


if __name__ == "__main__":
    main()
