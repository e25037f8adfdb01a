"""Kind ``eval_sweep``: each call one ``sweep.eval_sweep`` over
``points`` random in-window partitions drawn from the seed (pair moves of
the uniform partition that keep every sum), under the congestion model
the traffic file names.

The check compares a sample of the window's records, drawn from the
seed, with the reference's, over every field of the record.
"""
from __future__ import annotations

from pathlib import Path

from bench.harness import generate as gen
from bench.harness.check import EVAL_KEYS, reference_of, rel_err
from bench.harness.sut import plain

#: The checkout this kind was loaded from; its ``bench/reference`` holds
#: the configuration's reference.
ROOT = Path(__file__).resolve().parents[2]


def call(traffic: dict, cfg: dict, seed: int, i: int) -> dict:
    ref = reference_of(cfg, ROOT)
    Px, Py, co = gen.partitions(gen.rng(seed, 1, i),
                                ref.graph_ops(cfg["workload"]),
                                ref.package(cfg), traffic["points"],
                                traffic.get("steps", 2))
    return {"congestion": traffic["congestion"], "Px": Px, "Py": Py,
            "collectors": co, "designs": traffic["points"]}


def warm_calls(traffic: dict, cfg: dict, seed: int) -> list[dict]:
    return [call(traffic, cfg, seed, gen.WARM)]


def prepare(system, c: dict) -> list:
    return [system.eval_point(c["Px"][k], c["Py"][k], c["collectors"][k],
                              c["congestion"])
            for k in range(len(c["Px"]))]


def run(system, c: dict, args, cache: bool = True) -> list:
    return system.sweep.eval_sweep(args, backend="jax", cache=cache,
                                   devices=system.devices)


def check(checks, ref, traffic: dict, w, seed: int) -> None:
    """``check.sample`` records of the window (the flow reference takes
    about half a second a point)."""
    for a in w.answers:
        checks.count("calls_failed", len(a["out"]) != len(a["call"]["Px"]))
    rows = [(a, k) for a in w.answers for k in range(len(a["out"]))]
    n = min(traffic["check"]["sample"], len(rows))
    for j in sorted(gen.rng(seed, 4).choice(len(rows), n, replace=False)):
        a, k = rows[j]
        c = a["call"]
        scorer = ref.scorer(congestion=c["congestion"])
        got = plain(a["out"][k])
        want = scorer.evaluate(c["Px"][k], c["Py"][k], c["collectors"][k],
                               scorer.redist_of())
        checks.worst(f"eval_{c['congestion']}_rel_err",
                     max(rel_err(got[key], want[key]) for key in EVAL_KEYS))
