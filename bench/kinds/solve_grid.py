"""Kind ``solve_grid``: each call one ``sweep.solve_grid`` over the
package variants the traffic file lists (every combination of its
``variants`` fields), one island per variant, at the solver budget the
file states; a new solver seed per call, the objective cycling through
``objectives``.

The check re-scores every island's best genome with the reference, holds
the engine to the stated budget and to a monotone best-so-far history
that ends at the stated objective, and counts the islands whose search
never improved on its generation 0: a search that does not search leaves
every island there.
"""
from __future__ import annotations

import numpy as np

from bench.harness import generate as gen
from bench.harness.check import rel_err
from bench.harness.sut import plain


def call(traffic: dict, cfg: dict, seed: int, i: int) -> dict:
    objs = traffic["objectives"]
    vs = gen.variants(traffic["variants"])
    s = traffic["solver"]
    return {"method": traffic["method"], "objective": objs[i % len(objs)],
            "variants": vs, "solver": dict(s, seed=gen.derived_seed(seed, i)),
            "designs": len(vs) * s["population"] * s["generations"]}


def warm_calls(traffic: dict, cfg: dict, seed: int) -> list[dict]:
    """One call per objective."""
    return [call(traffic, cfg, seed, gen.WARM + k)
            for k in range(len(traffic["objectives"]))]


def prepare(system, c: dict):
    return ([system.solve_point(v) for v in c["variants"]],
            system.ga_config(c["solver"]))


def run(system, c: dict, args, cache: bool = True) -> list:
    pts, ga = args
    return system.sweep.solve_grid(pts, c["objective"], ga, backend="jax",
                                   cache=cache, method=c["method"],
                                   devices=system.devices)


def check(checks, ref, traffic: dict, w, seed: int) -> None:
    """Every island of every call of the window."""
    islands = unmoved = 0
    for a in w.answers:
        c = a["call"]
        checks.count("calls_failed", len(a["out"]) != len(c["variants"]))
        for v, got in zip(c["variants"], a["out"]):
            got = plain(got)
            h = np.asarray(got["history"], dtype=np.float64)
            islands += 1
            unmoved += bool(not len(h) or h[-1] >= h[0])
            one(checks, ref, got, h, v, c["objective"], c["solver"])
    checks.worst("solve_unmoved_share", unmoved / islands if islands else 1.0)


def one(checks, ref, got: dict, h: np.ndarray, variant: dict,
        objective: str, solver: dict) -> None:
    """One island: its budget, its history, its genome re-scored."""
    scorer = ref.scorer(variant)
    budget = solver["population"] * solver["generations"]
    checks.count("solve_budget_mismatch",
                 got["evaluations"] != budget
                 or len(h) != solver["generations"])
    checks.count("solve_history_fault",
                 not len(h) or bool(np.any(np.diff(h) > 0))
                 or h[-1] != got["objective"])
    bad = scorer.check_partition(got["Px"], got["Py"], got["collectors"])
    checks.count("solve_invalid_genome", bad is not None)
    if bad is not None:
        checks.worst("solve_rescore_rel_err", float("inf"))
        return
    want = scorer.evaluate(got["Px"], got["Py"], got["collectors"],
                           scorer.redist_of(got["redist_mask"]))[objective]
    checks.worst("solve_rescore_rel_err", rel_err(got["objective"], want))
