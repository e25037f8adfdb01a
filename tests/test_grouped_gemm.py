"""Uneven grouped GEMMs (``GemmOp.group_sizes``, DESIGN.md §5): the
grouped cost term in both evaluators, its reduction to the plain op, the
engines that take it and those that refuse it, and the records of tasks
without grouped ops, bitwise as before the term existed."""
import contextlib
import dataclasses
import hashlib

import numpy as np
import pytest

from repro.core import EvalOptions, Evaluator, GemmOp, Task, make_hw, sweep
from repro.core import evaluator_jax, ga_jax
from repro.core.ga import GAConfig, _random_population_vec
from repro.core.sweep import EvalPoint
from repro.core.workload import Partition, group_counts
from repro.core.x64 import x64
from repro.graphs import WORKLOADS, alexnet_task, deepseek_v2_task, vit_task

RTOL = 1e-9
KEYS = ("latency", "energy", "edp", "t_in", "t_comp", "t_out", "E_sram",
        "E_mac", "E_mem", "E_nop")
OPTS = EvalOptions(redistribution=True, async_exec=True)


def expert_counts(rng, experts: int, total: int) -> list[int]:
    """Uneven tokens per expert, some experts empty."""
    w = rng.gamma(0.7, size=experts) * (rng.random(experts) > 0.15)
    w[0] += 1e-3
    counts = np.floor(w / w.sum() * total).astype(int)
    counts[np.argmax(w)] += total - counts.sum()
    return counts.tolist()


def small_dsv2(seed: int = 0, layers_moe: int = 2) -> Task:
    """DeepSeek-V2's graph at reduced widths: 16 experts, top 2."""
    rng = np.random.default_rng(seed)
    tokens = 64
    return deepseek_v2_task(
        tokens=tokens, layers_dense=1, layers_moe=layers_moe,
        expert_tokens=[expert_counts(rng, 16, 2 * tokens)
                       for _ in range(layers_moe)],
        d_model=64, d_ff=96, n_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, n_experts=16,
        n_shared_experts=1, moe_top_k=2, moe_d_ff=32, vocab_size=256)


def population(task, hw, P: int, seed: int = 1):
    """``P`` random in-window genomes (the GA's own initial draw)."""
    return _random_population_vec(np.random.default_rng(seed), task, hw,
                                  GAConfig(population=P), P)


HWS = {"A4": make_hw("A", 4, "hbm", R=8, C=8),
       "B4": make_hw("B", 4, "dram", R=8, C=8)}


# ------------------------------------------------------------ the op
def test_gemm_op_validates_groups():
    op = GemmOp("e", M=10, K=4, N=4, group_sizes=[3, 0, 7])
    assert op.group_sizes == (3, 0, 7)
    hash(op)
    with pytest.raises(ValueError, match="sum to M"):
        GemmOp("e", M=10, K=4, N=4, group_sizes=(3, 6))
    with pytest.raises(ValueError, match="sum to M"):
        GemmOp("e", M=10, K=4, N=4, group_sizes=(11, -1))
    with pytest.raises(ValueError, match="weight_bytes_scale"):
        GemmOp("e", M=10, K=4, N=4, group_sizes=(10,),
               weight_bytes_scale=2.0)


def test_task_group_tables():
    ops = [GemmOp("a", M=8, K=4, N=4),
           GemmOp("b", M=10, K=4, N=4, group_sizes=(3, 0, 7)),
           GemmOp("c", M=6, K=4, N=4, group_sizes=(6,))]
    task = Task("t", ops)
    assert task.group_shape == (2, 3)
    arr = task.arrays()
    np.testing.assert_array_equal(arr["g_idx"], [1, 2])
    np.testing.assert_array_equal(arr["g_off"], [[0, 3, 3, 10],
                                                 [0, 6, 6, 6]])
    assert group_counts([task, task]) == {"grouped_ops": 4, "groups": 8}


def test_dsv2_graph_shape():
    task = small_dsv2(layers_moe=4)
    assert len(task) == 8 + 4 * 11 + 1
    names = [op.name.split(".")[-1] for op in task.ops]
    grouped = [op for op in task.ops if op.group_sizes]
    assert len(grouped) == 8
    assert {op.name.split(".")[-1] for op in grouped} == {
        "experts_up", "experts_down"}
    chained = {op.name.split(".")[-1] for op in task.ops if op.chained}
    assert chained == {"qkv_a", "mlp_up", "mlp_down", "router",
                       "shared_down", "experts_down"}
    # qkv_a is chained only after the dense layer's mlp_down
    assert [op.chained for op in task.ops
            if op.name.endswith("qkv_a")] == [False, True, False, False,
                                             False]
    assert names[-1] == "lm_head"
    assert WORKLOADS["deepseek_v2"] is deepseek_v2_task
    with pytest.raises(ValueError, match="expert_tokens"):
        deepseek_v2_task(tokens=4, layers_dense=1, layers_moe=2,
                         expert_tokens=[[24] * 1])


# ------------------------------------------------------ the grouped term
def brute_force(task, hw, Px):
    """The grouped term by loops over chiplet rows and experts: per op,
    groups each chiplet row needs, row tiles, and the groups each
    entrance fetches for the rows it serves."""
    top = hw.topology
    out = []
    for i, op in enumerate(task.ops):
        if not op.group_sizes:
            continue
        bounds = np.concatenate([[0], np.cumsum(op.group_sizes)])
        need = np.zeros((hw.X, len(op.group_sizes)), dtype=bool)
        tiles = np.zeros(hw.X)
        start = 0
        for x in range(hw.X):
            end = start + int(Px[i, x])
            for g in range(len(op.group_sizes)):
                rows = max(0, min(end, bounds[g + 1]) - max(start, bounds[g]))
                if rows:
                    need[x, g] = True
                    tiles[x] += -(-rows // hw.R)
            start = end
        fetch = [need[top.entrance_rows[e]].any(axis=0).sum()
                 for e in range(top.n_entrances)]
        out.append((need.sum(axis=1), tiles, np.array(fetch)))
    return out


@pytest.mark.parametrize("hw", list(HWS.values()), ids=list(HWS))
def test_grouped_term_matches_brute_force(hw):
    task = small_dsv2(3)
    ev = Evaluator(task, hw, OPTS)
    Px = population(task, hw, 5)[0]
    got = ev._group_terms(Px)
    c = {k: np.asarray(v) for k, v in ev.consts().items()}
    for p in range(len(Px)):
        with x64():
            gj = {k: np.asarray(v) for k, v in
                  evaluator_jax._group_terms(c, Px[p]).items()}
        for j, (t, tiles, fetch) in zip(ev.g_idx,
                                        brute_force(task, hw, Px[p])):
            for terms in (got, gj):
                sel = (lambda a: a[p, j]) if terms is got else \
                    (lambda a: a[j])
                np.testing.assert_array_equal(sel(terms["rows"]), t)
                np.testing.assert_array_equal(sel(terms["tiles"]), tiles)
                np.testing.assert_array_equal(sel(terms["fetch"]), fetch)
                assert sel(terms["held"]) == t.sum()
    # an op without groups keeps today's factors
    plain = [i for i in range(len(task)) if i not in set(ev.g_idx)]
    np.testing.assert_array_equal(got["rows"][:, plain], 1.0)
    np.testing.assert_array_equal(got["tiles"][:, plain],
                                  np.ceil(Px[:, plain] / hw.R))


@pytest.mark.parametrize("congestion", ["regime", "flow"])
@pytest.mark.parametrize("hw", list(HWS.values()), ids=list(HWS))
def test_numpy_matches_jax(hw, congestion):
    task = small_dsv2(1)
    opts = dataclasses.replace(OPTS, congestion=congestion)
    Px, Py, co, rd = population(task, hw, 6, seed=2)
    want = Evaluator(task, hw, opts).evaluate_batch(Px, Py, co, rd)
    got = Evaluator(task, hw, opts, backend="jax").evaluate_batch(
        Px, Py, co, rd)
    for k in KEYS:
        np.testing.assert_allclose(got[k], want[k], rtol=RTOL, err_msg=k)


def test_grouped_term_moves_the_cost():
    """Expert rows that straddle chiplet rows cost weight traffic and
    tile padding that a flattened op would not see."""
    task = small_dsv2(4)
    hw = HWS["A4"]
    flat = Task("flat", [dataclasses.replace(op, group_sizes=(op.M,))
                         if op.group_sizes else op for op in task.ops])
    Px, Py, co, rd = population(task, hw, 4, seed=3)
    grouped = Evaluator(task, hw, OPTS).evaluate_batch(Px, Py, co, rd)
    one = Evaluator(flat, hw, OPTS).evaluate_batch(Px, Py, co, rd)
    assert (grouped["E_sram"] > one["E_sram"]).all()
    assert (grouped["latency"] > one["latency"]).all()


@pytest.mark.parametrize("congestion", ["regime", "flow"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_one_group_is_the_plain_op(backend, congestion):
    """One group of all M rows is today's op with w_scale 1, bitwise, on
    partitions whose chiplet rows all hold rows."""
    hw = HWS["A4"]
    base = vit_task(depth=1, d=64, heads=4, tokens=33, patch_dim=48)
    ops = list(base.ops)
    for i in (1, 4):
        assert ops[i].weight_bytes_scale == 1.0
        ops[i] = dataclasses.replace(ops[i], group_sizes=(ops[i].M,))
    task = Task(base.name, ops)
    opts = dataclasses.replace(OPTS, congestion=congestion)
    Px, Py, co, rd = population(base, hw, 5, seed=4)
    assert (Px[:, [1, 4]] > 0).all()
    want = Evaluator(base, hw, opts, backend=backend).evaluate_batch(
        Px, Py, co, rd)
    got = Evaluator(task, hw, opts, backend=backend).evaluate_batch(
        Px, Py, co, rd)
    for k in KEYS:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


# ------------------------------------------- tasks without grouped ops
def record_digest(task, hw) -> str:
    """Records of both backends under both congestion models on four
    in-window partitions, and a two-island GA solve."""
    h = hashlib.sha256()
    cfg = GAConfig(population=8, generations=3, patience=3, seed=5,
                   backend="jax")
    Px, Py, co, rd = _random_population_vec(np.random.default_rng(11), task,
                                            hw, cfg, 4)
    for congestion in ("regime", "flow"):
        opts = dataclasses.replace(OPTS, congestion=congestion)
        pts = [EvalPoint(task, hw, opts,
                         Partition(Px[k].astype(np.int64),
                                   Py[k].astype(np.int64),
                                   co[k].astype(np.int64)), rd[k] > 0.5)
               for k in range(4)]
        for backend in ("numpy", "jax"):
            for rec in sweep.eval_sweep(pts, backend=backend, cache=False,
                                        devices="single"):
                for k in KEYS:
                    h.update(np.asarray(rec[k], dtype=np.float64).tobytes())
    pts = [EvalPoint(task, make_hw("A", hw.X, "hbm", diagonal_links=d), OPTS)
           for d in (False, True)]
    for r in sweep.solve_grid(pts, "edp", cfg, backend="jax", cache=False,
                              devices="single"):
        for a in (r.partition.Px, r.partition.Py, r.partition.collectors,
                  r.redist_mask, r.history, np.float64(r.objective)):
            h.update(np.asarray(a).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name,task,want", [
    ("vit", vit_task(depth=2, d=64, heads=4, tokens=17, patch_dim=48),
     "1b08cb867290bed741e44c11e60dd8289321ddb860ac38a3d8c065276b9e6e94"),
    ("alexnet", alexnet_task(),
     "4cc8dc0931a06f8ac710d6aec1fb89469dfc83397d10af3ec1f078b61d11cbbf"),
], ids=["vit", "alexnet"])
def test_records_without_groups_unchanged(name, task, want):
    """Digests recorded on this machine's CPU before grouped ops
    existed."""
    assert record_digest(task, make_hw("A", 4, "hbm")) == want


def test_no_group_tables_without_grouped_ops():
    """A task without grouped ops ships no group tables, so its compiled
    programs are the ones it had before they existed."""
    ev = Evaluator(vit_task(depth=1), make_hw("A", 4, "hbm"), OPTS)
    assert not set(evaluator_jax.GROUP_KEYS) & set(ev.consts())
    ev = Evaluator(small_dsv2(), make_hw("A", 4, "hbm"), OPTS)
    assert set(evaluator_jax.GROUP_KEYS) <= set(ev.consts())


# ------------------------------------------------------------ engines
def test_ga_solo_equals_batched():
    task = small_dsv2(5)
    hws = [make_hw("A", 4, "hbm", R=8, C=8, diagonal_links=d, bw_nop=b)
           for d, b in ((False, 60e9), (True, 60e9), (False, 15e9))]
    cfg = GAConfig(population=12, generations=6, patience=6, seed=3,
                   backend="jax", p_mutate_partition=0.2)
    pts = [EvalPoint(task, h, OPTS) for h in hws]
    batched = sweep.solve_grid(pts, "edp", cfg, backend="jax", cache=False,
                               devices="single")
    for pt, b in zip(pts, batched):
        solo = sweep.solve_grid([pt], "edp", cfg, backend="jax",
                                cache=False, devices="single")[0]
        np.testing.assert_array_equal(solo.partition.Px, b.partition.Px)
        np.testing.assert_array_equal(solo.partition.Py, b.partition.Py)
        np.testing.assert_array_equal(solo.partition.collectors,
                                      b.partition.collectors)
        np.testing.assert_array_equal(solo.history, b.history)
        assert solo.objective == b.objective
        ev = Evaluator(task, pt.hw, OPTS)
        rescored = ev.evaluate(b.partition, b.redist_mask).edp
        assert abs(rescored - b.objective) <= RTOL * abs(b.objective)


def test_expert_tokens_key_the_sweep_cache():
    a, b = small_dsv2(6), small_dsv2(7)
    assert [dataclasses.replace(op, group_sizes=()) for op in a.ops] == \
        [dataclasses.replace(op, group_sizes=()) for op in b.ops]
    hw = HWS["A4"]
    sweep.clear_cache()
    ra = sweep.eval_sweep([EvalPoint(a, hw, OPTS)], backend="numpy")[0]
    rb = sweep.eval_sweep([EvalPoint(b, hw, OPTS)], backend="numpy")[0]
    assert sweep.cache_stats() == {"hits": 0, "misses": 2}
    assert ra["latency"] != rb["latency"]
    sweep.eval_sweep([EvalPoint(a, hw, OPTS)], backend="numpy")
    assert sweep.cache_stats()["hits"] == 1
    sweep.clear_cache()


def test_miqp_lattice_takes_groups():
    from repro.core.miqp import MIQPConfig, run_miqp

    task, hw = small_dsv2(8, layers_moe=1), HWS["A4"]
    cfg = MIQPConfig(engine="lattice")
    res = run_miqp(task, hw, "latency", OPTS, cfg)
    want = Evaluator(task, hw, OPTS).evaluate(res.partition,
                                              res.redist_mask).latency
    assert abs(res.objective - want) <= RTOL * want


def test_engines_that_cannot_take_groups_refuse():
    from repro.core.cosearch import CoSearchConfig, run_cosearch
    from repro.core.miqp import MIQPConfig, run_miqp
    from repro.core.multitenant import solve_multitenant

    task, hw = small_dsv2(), HWS["A4"]
    with pytest.raises(ValueError, match="grouped GEMMs"):
        run_cosearch(task, hw, "edp", OPTS, CoSearchConfig())
    with pytest.raises(ValueError, match="grouped GEMMs"):
        run_miqp(task, hw, "latency", OPTS, MIQPConfig(engine="milp"))
    with pytest.raises(ValueError, match="grouped GEMMs"):
        solve_multitenant([task, vit_task(depth=1)], hw)


def test_ga_spans_carry_group_counts(monkeypatch):
    seen = []

    class Span(contextlib.AbstractContextManager):
        def __init__(self, name, **args):
            self.args = dict(args)
            seen.append((name, self.args))

        def set_metadata(self, **args):
            self.args.update(args)

        def __exit__(self, *exc):
            return None

    monkeypatch.setattr(ga_jax, "span", Span)
    task = small_dsv2(9)
    hws = [HWS["A4"], dataclasses.replace(HWS["A4"], diagonal_links=True)]
    cfg = GAConfig(population=8, generations=2, patience=2, seed=1,
                   backend="jax")
    res = ga_jax.solve_islands([task, task], hws, OPTS, "edp", cfg,
                               devices="single")
    args = dict(seen)
    assert args["ga.consts"]["grouped_ops"] == 2 * 4
    assert args["ga.consts"]["groups"] == 2 * 4 * 16
    evs = [Evaluator(task, h, OPTS) for h in hws]
    want = sum(ga_jax.fetch_excess(ev, r.partition)
               for ev, r in zip(evs, res))
    assert args["ga.results"]["expert_fetch_excess"] == want >= 0
