"""``congestion="flow"`` evaluator mode (DESIGN.md §11): backend parity,
sweep round-trips, cache keying on the congestion axis, and GA solves
under simulated contention."""
import numpy as np
import pytest

from repro.core import (EvalOptions, Evaluator, GemmOp, Task, make_hw,
                        sweep, uniform_partition)
from repro.core.ga import GAConfig


def toy_task(n=3):
    ops = [GemmOp("g0", M=512, K=256, N=512)]
    for i in range(1, n):
        ops.append(GemmOp(f"g{i}", M=512, K=ops[-1].N, N=512,
                          chained=True, sync=(i == 1)))
    return Task(f"flowtoy{n}", ops)


@pytest.fixture(autouse=True)
def _fresh_cache():
    sweep.clear_cache()
    yield
    sweep.clear_cache()


def test_bad_congestion_rejected():
    with pytest.raises(ValueError):
        EvalOptions(congestion="astral")
    with pytest.raises(ValueError):
        Evaluator(toy_task(), make_hw("A", 4), congestion="astral")


def test_ctor_override_merges_into_options():
    ev = Evaluator(toy_task(), make_hw("A", 4),
                   EvalOptions(redistribution=True), congestion="flow")
    assert ev.opts.congestion == "flow"
    assert ev.opts.redistribution is True


@pytest.mark.parametrize("t", list("ABCD"))
def test_flow_mode_backend_parity(t):
    """numpy reference vs jax traced netsim, all packaging types."""
    task = toy_task()
    hw = make_hw(t, 4, "hbm", diagonal_links=(t == "A"))
    part = uniform_partition(task, 4, 4)
    opts = EvalOptions(redistribution=True, async_exec=True,
                       congestion="flow")
    rn = Evaluator(task, hw, opts, backend="numpy").evaluate(part)
    rj = Evaluator(task, hw, opts, backend="jax").evaluate(part)
    assert rj.latency == pytest.approx(rn.latency, rel=1e-9)
    assert rj.energy == pytest.approx(rn.energy, rel=1e-9)
    np.testing.assert_allclose(rj.t_in, rn.t_in, rtol=1e-9)
    np.testing.assert_allclose(rj.t_out, rn.t_out, rtol=1e-9)


def test_flow_mode_batch_parity():
    task = toy_task(2)
    hw = make_hw("A", 4, "hbm")
    opts = EvalOptions(congestion="flow")
    rng = np.random.default_rng(0)
    base = uniform_partition(task, 4, 4)
    P = 4
    Px = np.repeat(base.Px[None], P, 0).astype(float)
    Py = np.repeat(base.Py[None], P, 0).astype(float)
    co = rng.integers(0, 4, (P, 2))
    rd = np.zeros((P, 2))
    a = Evaluator(task, hw, opts, backend="numpy").evaluate_batch(
        Px, Py, co, rd)
    b = Evaluator(task, hw, opts, backend="jax").evaluate_batch(
        Px, Py, co, rd)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=1e-9, err_msg=k)


def test_flow_differs_from_regime_and_energy_matches():
    """The two congestion models must disagree on latency for a congested
    HBM mesh (else "flow" is a no-op) while agreeing on energy — the
    byte×hop accounting is congestion-independent."""
    task = toy_task()
    hw = make_hw("A", 4, "hbm")
    part = uniform_partition(task, 4, 4)
    r = Evaluator(task, hw, congestion="regime").evaluate(part)
    f = Evaluator(task, hw, congestion="flow").evaluate(part)
    assert f.latency != pytest.approx(r.latency, rel=1e-6)
    assert f.energy == pytest.approx(r.energy, rel=1e-12)


def test_flow_equals_regime_on_type_c():
    """Type C stacks memory on every chiplet: no data touches the mesh,
    so the flow simulation must collapse to the closed-form off-chip
    terms — flow == regime exactly. Pins the §11 accounting split
    (per-entrance multicast off-chip term + mesh-only simulated flows);
    per-chiplet port pulls would break this identity."""
    task = toy_task()
    hw = make_hw("C", 4, "hbm")
    part = uniform_partition(task, 4, 4)
    for backend in ("numpy", "jax"):
        r = Evaluator(task, hw, congestion="regime",
                      backend=backend).evaluate(part)
        f = Evaluator(task, hw, congestion="flow",
                      backend=backend).evaluate(part)
        assert f.latency == pytest.approx(r.latency, rel=1e-12)
        np.testing.assert_allclose(f.t_in, r.t_in, rtol=1e-12)
        np.testing.assert_allclose(f.t_out, r.t_out, rtol=1e-12)


def test_eval_sweep_congestion_axis_round_trip():
    """EvalPoints on a congestion axis batch, cache per mode, and match
    the direct evaluator."""
    task = toy_task()
    hw = make_hw("A", 4, "hbm")
    pts = [sweep.EvalPoint(task, hw, EvalOptions(congestion=c))
           for c in ("regime", "flow")]
    recs = sweep.eval_sweep(pts, backend="jax")
    assert sweep.cache_stats() == {"hits": 0, "misses": 2}
    for pt, rec in zip(pts, recs):
        ref = Evaluator(task, hw, pt.options).evaluate(
            uniform_partition(task, 4, 4))
        assert rec["latency"] == pytest.approx(ref.latency, rel=1e-9)
    # repeat hits the cache, keyed on the congestion axis
    again = sweep.eval_sweep(pts, backend="jax")
    assert sweep.cache_stats() == {"hits": 2, "misses": 2}
    assert again[0]["latency"] != again[1]["latency"]


def test_solve_grid_under_flow_congestion():
    """GA searches optimize under simulated contention (tiny budget) and
    cache under the flow-keyed fingerprint."""
    task = toy_task(2)
    opts = EvalOptions(redistribution=True, async_exec=True,
                       congestion="flow")
    cfg = GAConfig(generations=2, population=8, patience=2, seed=0)
    pts = [sweep.EvalPoint(task, make_hw("A", 2, "hbm"), opts)]
    recs = sweep.solve_grid(pts, "latency", cfg, backend="jax")
    assert np.isfinite(recs[0].objective) and recs[0].objective > 0
    assert sweep.cache_stats()["misses"] >= 1
    again = sweep.solve_grid(pts, "latency", cfg, backend="jax")
    assert sweep.cache_stats()["hits"] >= 1
    assert again[0].objective == recs[0].objective


# ------------------------------------------- compacted flow sites (§11)
def _moved_genomes(task, hw, P, seed):
    """``[P, n, ...]`` genomes: the uniform partition with a few rows and
    columns moved between chiplets per op, random collectors and
    redistribution flags."""
    rng = np.random.default_rng(seed)
    base = uniform_partition(task, hw.X, hw.Y)
    Px = np.repeat(base.Px[None], P, 0).astype(float)
    Py = np.repeat(base.Py[None], P, 0).astype(float)
    for A in (Px, Py):
        for p in range(P):
            for i in range(len(task)):
                a, b = rng.choice(A.shape[-1], 2, replace=False)
                step = min(A[p, i, a], 16.0 * rng.integers(1, 4))
                A[p, i, a] -= step
                A[p, i, b] += step
    co = rng.integers(0, hw.Y, (P, len(task))).astype(float)
    rd = rng.integers(0, 2, (P, len(task))).astype(float)
    return Px, Py, co, rd


def _grid_run(consts, opts, genomes):
    """Records and lane counts of one grid call (grid axis of 1)."""
    from repro.core import evaluator_jax

    stacked = {k: np.asarray(v)[None] for k, v in consts.items()}
    return evaluator_jax._run_x64(
        evaluator_jax.grid_fn(*evaluator_jax._static_key(opts)), stacked,
        *(g[None] for g in genomes))


def _full_width(consts, top):
    """``consts`` with both flow sites on the whole link axis."""
    cap, dist, coll = top.flow_net()
    return consts | {"flow_cap": np.stack([cap, cap]), "dist_inc": dist,
                     "coll_inc": coll}


def _assert_bitwise(a, b):
    assert sorted(a) == sorted(b)
    for k in a:
        assert a[k].dtype == b[k].dtype and a[k].tobytes() == b[k].tobytes(), k


@pytest.mark.parametrize("grid", [4, 8])
@pytest.mark.parametrize("diagonal", [False, True])
@pytest.mark.parametrize("memory", ["hbm", "dram"])
@pytest.mark.parametrize("t", list("ABD"))
def test_flow_sites_compacted_bitwise(t, memory, diagonal, grid):
    """Each flow site runs over the X·Y-wide axis of the links its flows
    use, and gives bitwise the records and lane counts of the whole
    link axis."""
    task = toy_task()
    hw = make_hw(t, grid, memory, diagonal_links=diagonal)
    opts = EvalOptions(redistribution=True, async_exec=True,
                       congestion="flow")
    consts = Evaluator(task, hw, opts, backend="jax").consts()
    top = hw.topology
    cap, dist, coll = top.flow_net()
    W = grid * grid
    assert consts["flow_cap"].shape == (2, W)
    for site, full in enumerate((dist, coll)):
        used = full.any(axis=0)
        inc = consts[("dist_inc", "coll_inc")[site]]
        assert inc.shape == (W, W)
        k = int(used.sum())
        assert 0 < k < W
        np.testing.assert_array_equal(inc[:, :k], full[:, used])
        assert not inc[:, k:].any()
        np.testing.assert_array_equal(consts["flow_cap"][site, :k],
                                      cap[used])
        assert (consts["flow_cap"][site, k:] == 1.0).all()
    genomes = _moved_genomes(task, hw, 3, seed=grid)
    out, lanes = _grid_run(consts, opts, genomes)
    out_full, lanes_full = _grid_run(_full_width(consts, top), opts, genomes)
    _assert_bitwise(out, out_full)
    _assert_bitwise(lanes, lanes_full)
    assert lanes["dist_events"].sum() > 0


def test_flow_sites_fallback_keeps_full_axis():
    """A network whose flows use more than X·Y links at a site keeps the
    whole link axis at both sites, still matches the numpy reference,
    and gets a shape group of its own in ``eval_sweep``."""
    import dataclasses

    task = toy_task()
    opts = EvalOptions(redistribution=True, async_exec=True,
                       congestion="flow")
    hw = make_hw("A", 4, "hbm")
    hand = dataclasses.replace(hw, bw_nop=30e9)
    cap, dist, coll = hand.topology.flow_net()
    # every distribution flow also crosses its collection route
    hand.topology._flow_net = (cap, dist + coll, coll)
    L = cap.shape[0]
    assert (dist + coll).any(axis=0).sum() > 16
    consts = Evaluator(task, hand, opts, backend="jax").consts()
    assert consts["flow_cap"].shape == (2, L)
    assert consts["dist_inc"].shape == consts["coll_inc"].shape == (16, L)
    genomes = _moved_genomes(task, hand, 2, seed=1)
    out, lanes = _grid_run(consts, opts, genomes)
    out_full, lanes_full = _grid_run(_full_width(consts, hand.topology),
                                     opts, genomes)
    _assert_bitwise(out, out_full)
    _assert_bitwise(lanes, lanes_full)
    ref = Evaluator(task, hand, opts, backend="numpy").evaluate_batch(
        *genomes)
    for k in ref:
        np.testing.assert_allclose(out[k][0], ref[k], rtol=1e-9, err_msg=k)
    # mixed widths in one eval_sweep: two groups, each its reference
    part = uniform_partition(task, 4, 4)
    pts = [sweep.EvalPoint(task, h, opts, part) for h in (hw, hand, hw)]
    recs = sweep.eval_sweep(pts, backend="jax", cache=False)
    for pt, rec in zip(pts, recs):
        want = Evaluator(task, pt.hw, opts).evaluate(part)
        assert rec["latency"] == pytest.approx(want.latency, rel=1e-9)
    assert recs[0]["latency"] == recs[2]["latency"] != recs[1]["latency"]


@pytest.mark.parametrize("t", list("ABCD"))
def test_regime_consts_keep_flow_placeholders(t):
    """Regime mode never reads the flow network and ships 1-element
    placeholders for it, as it did before the sites were compacted."""
    consts = Evaluator(toy_task(), make_hw(t, 4, "hbm"),
                       EvalOptions(congestion="regime"),
                       backend="jax").consts()
    for k in ("flow_cap", "dist_inc", "coll_inc"):
        assert consts[k].dtype == np.float64
        np.testing.assert_array_equal(consts[k], np.zeros(1))
