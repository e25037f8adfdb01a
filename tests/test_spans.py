"""The program's spans (``repro.runtime.spans``) on the profiler's clock:
a flow ``eval_sweep`` and a GA ``solve_grid`` traced on the CPU leave
every span of their layers, nested in the call's outer span on the
caller's thread, with their arguments; and tracing changes no record."""
import dataclasses
import glob
import os
import warnings

import numpy as np
import pytest

import jax

from repro.core import EvalOptions, GAConfig, evaluator_jax, make_hw, sweep
from repro.core.evaluator import Evaluator
from repro.graphs import WORKLOADS
from repro.runtime.spans import PREFIX

EVAL_SPANS = {
    "sweep.eval": {"call", "points"},
    "sweep.lookup": {"hits", "misses"},
    "sweep.group": {"groups"},
    "sweep.consts": {"points", "bytes", "grouped_ops", "groups"},
    "eval.to_device": {"bytes"},
    "eval.call": set(),
    "eval.fetch": set(),
    "eval.lanes": {"site", "lanes", "events_sum", "events_max",
                   "fills_sum", "fills_max", "links", "links_used"},
    "sweep.records": {"records"},
}
SOLVE_SPANS = {
    "sweep.solve": {"call", "points"},
    "sweep.lookup": {"hits", "misses"},
    "sweep.group": {"groups"},
    "ga.consts": {"islands", "bytes", "grouped_ops", "groups"},
    "ga.init": {"islands", "population", "distinct"},
    "ga.to_device": {"bytes"},
    "ga.chunk": {"generations"},
    "ga.results": set(),
    "sweep.records": {"records"},
}


def _traced(tmp_path, fn):
    """``fn()`` under the profiler (annotations only on the host); returns
    its result and the program's spans as ``(thread, name, start, end,
    args)``, in start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    with warnings.catch_warnings():
        # iterating a stats view warns about its builtin type's module
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for k, line in enumerate(plane.lines):
                spans += [((plane.name, k), e.name[len(PREFIX):],
                           int(e.start_ns), int(e.start_ns + e.duration_ns),
                           dict(e.stats))
                          for e in line.events if e.name.startswith(PREFIX)]
    return out, sorted(spans, key=lambda s: s[2])


def _calls(spans, outer: str, table: dict) -> list[dict]:
    """Each outer span's inner spans, by name; checks that they nest
    inside it on its thread, are spans of the table, and carry exactly
    its arguments."""
    calls = []
    for thread, name, s0, e0, args in spans:
        if name != outer:
            continue
        inner: dict[str, list[dict]] = {}
        for t, n, s, e, a in spans:
            if s0 <= s and e <= e0 and n != outer:
                assert t == thread, n
                inner.setdefault(n, []).append(a)
        inner[outer] = [args]
        assert set(inner) <= set(table), set(inner) - set(table)
        for n, arg_list in inner.items():
            for a in arg_list:
                assert set(a) == table[n], (n, a)
        calls.append(inner)
    return calls


def _same(a, b) -> bool:
    if isinstance(a, np.ndarray):
        return a.dtype == b.dtype and a.tobytes() == b.tobytes()
    return a == b


def test_eval_sweep_spans(tmp_path):
    task = WORKLOADS["alexnet"](batch=1)
    hw = make_hw("A", 4, "hbm")
    opts = EvalOptions(congestion="flow")
    points = [sweep.EvalPoint(task, dataclasses.replace(hw, bw_nop=b), opts)
              for b in (15e9, 30e9, 60e9)]
    untraced = sweep.eval_sweep(points, cache=False)
    sweep.clear_cache()
    try:
        traced, spans = _traced(tmp_path, lambda: [
            sweep.eval_sweep(points), sweep.eval_sweep(points)])
    finally:
        sweep.clear_cache()
    for recs in traced:
        for a, b in zip(untraced, recs):
            assert all(_same(a[k], b[k]) for k in a), a["latency"]

    first, second = _calls(spans, "sweep.eval", EVAL_SPANS)
    assert set(first) == set(EVAL_SPANS)
    assert second["sweep.eval"][0]["call"] == \
        first["sweep.eval"][0]["call"] + 1
    assert first["sweep.eval"] == [{"call": first["sweep.eval"][0]["call"],
                                    "points": 3}]
    assert first["sweep.lookup"] == [{"hits": 0, "misses": 3}]
    assert first["sweep.group"] == [{"groups": 1}]
    consts, = first["sweep.consts"]
    assert consts["points"] == 3
    assert consts["grouped_ops"] == consts["groups"] == 0
    # what the sweep driver stacked is what goes to the device
    assert first["eval.to_device"] == [{"bytes": consts["bytes"]}]
    assert first["sweep.records"] == [{"records": 3}, {"records": 3}]
    # the second call is answered from the cache: no engine work
    assert second["sweep.lookup"] == [{"hits": 3, "misses": 0}]

    # the lane markers are the sums of the engine's per-lane counts
    evs = [Evaluator(p.task, p.hw, p.options, backend="jax") for p in points]
    stacked = {k: np.stack([ev.consts()[k] for ev in evs])
               for k in evs[0].consts()}
    genomes = [np.stack([g[j] for g in (sweep._genome(p, ev)
                                        for p, ev in zip(points, evs))]
                        )[:, None] for j in range(4)]
    _, lanes = evaluator_jax._run_x64(
        evaluator_jax.grid_fn(*evaluator_jax._static_key(opts)), stacked,
        *genomes)
    assert sorted(lanes) == sorted(evaluator_jax.LANE_KEYS)
    marks = {m["site"]: m for m in first["eval.lanes"]}
    assert sorted(marks) == ["coll", "dist"]
    _, dist, coll = hw.topology.flow_net()
    for site, m in marks.items():
        ev_, fi = lanes[f"{site}_events"], lanes[f"{site}_fills"]
        assert ev_.shape == (3, 1, len(task))
        full = dist if site == "dist" else coll
        assert m == {"site": site, "lanes": 3 * len(task),
                     "events_sum": int(ev_.sum()),
                     "events_max": int(ev_.max()),
                     "fills_sum": int(fi.sum()), "fills_max": int(fi.max()),
                     "links": 16, "links_used": int(full.any(axis=0).sum())}
        assert 0 < m["events_sum"] <= m["lanes"] * m["events_max"]
        assert m["fills_sum"] >= m["events_sum"]


def test_eval_lanes_links(tmp_path):
    """``eval.lanes`` names the width of each flow site's link axis and
    the most links any point's flows use there: X·Y and the used links
    of the package's routes on a 4x4 grid, one group per package type;
    the whole link axis for a network whose flows use more than X·Y."""
    task = WORKLOADS["alexnet"](batch=1)
    opts = EvalOptions(congestion="flow")
    hws = [make_hw(t, 4, "hbm") for t in "ABD"]
    hand = dataclasses.replace(hws[0], bw_nop=30e9)
    cap, dist, coll = hand.topology.flow_net()
    hand.topology._flow_net = (cap, dist + coll, coll)
    hws.append(hand)
    points = [sweep.EvalPoint(task, h, opts) for h in hws]
    try:
        _, spans = _traced(tmp_path, lambda: sweep.eval_sweep(points))
    finally:
        sweep.clear_cache()
    call, = _calls(spans, "sweep.eval", EVAL_SPANS)
    assert call["sweep.group"] == [{"groups": 4}]
    marks = call["eval.lanes"]
    assert [m["site"] for m in marks] == ["dist", "coll"] * 4
    for j, h in enumerate(hws):
        _, d, c = h.topology.flow_net()
        used = [int(inc.any(axis=0).sum()) for inc in (d, c)]
        # both sites keep the whole axis if either uses more than X·Y
        width = 16 if max(used) <= 16 else cap.shape[0]
        assert [m["links_used"] for m in marks[2 * j:2 * j + 2]] == used
        assert [m["links"] for m in marks[2 * j:2 * j + 2]] == [width] * 2
    assert marks[0]["links_used"] == 15    # type A: a tree from the corner
    assert marks[6]["links"] == cap.shape[0] > 16


def test_solve_grid_spans(tmp_path):
    task = WORKLOADS["alexnet"](batch=1)
    hw = make_hw("A", 4, "hbm")
    points = [sweep.EvalPoint(task, dataclasses.replace(hw, bw_nop=b))
              for b in (15e9, 60e9)]
    cfg = GAConfig(population=8, generations=4, patience=2, seed=5,
                   backend="jax", p_mutate_partition=0.1)
    untraced = sweep.solve_grid(points, "edp", cfg, cache=False)
    sweep.clear_cache()
    try:
        traced, spans = _traced(
            tmp_path, lambda: sweep.solve_grid(points, "edp", cfg))
    finally:
        sweep.clear_cache()
    for a, b in zip(untraced, traced):
        for f in ("partition", "redist_mask", "objective", "history",
                  "evaluations"):
            x, y = getattr(a, f), getattr(b, f)
            if f == "partition":
                assert all(_same(getattr(x, k), getattr(y, k))
                           for k in ("Px", "Py", "collectors"))
            else:
                assert _same(x, y), f

    call, = _calls(spans, "sweep.solve", SOLVE_SPANS)
    assert set(call) == set(SOLVE_SPANS)
    assert call["sweep.solve"][0]["points"] == 2
    assert call["sweep.lookup"] == [{"hits": 0, "misses": 2}]
    # one task on one grid: both islands share one initial population
    assert call["ga.init"] == [{"islands": 2, "population": 8,
                                "distinct": 1}]
    consts, = call["ga.consts"]
    assert consts["islands"] == 2 and consts["bytes"] > 0
    assert consts["grouped_ops"] == consts["groups"] == 0
    # constants, windows and the initial genomes go to the device
    assert call["ga.to_device"][0]["bytes"] > consts["bytes"]
    assert call["ga.chunk"] == [{"generations": 2}] * len(call["ga.chunk"])
    assert 1 <= len(call["ga.chunk"]) <= 2
    assert call["sweep.records"] == [{"records": 2}]
