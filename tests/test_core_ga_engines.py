"""GA engine contract (DESIGN.md §10): python loop vs vectorized numpy
vs device-resident jax.

Exact numpy↔jax trajectory parity is impossible across RNGs, so the
cross-engine contract is property-based —

  * exact per-op partition sums (crossover/mutation are sum-preserving),
  * membership in the Sec-6.2 domain window (multiples of R within
    uniform ± slack),
  * the best objective never regresses across generations,
  * elitism: the final objective never loses to the uniform-partition
    individual seeded at index 0,

— plus fixed-seed solution-quality equivalence: the vectorized engine's
final objective lands within 1% of the python engine's (median over 5
seeds) on alexnet/vit. Hypothesis drives randomized instances of the
operator-level invariants when installed (tests/_hypothesis_compat.py
skips them otherwise; the seeded parametrized tests below always run).
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import (AUTO_POPULATION_THRESHOLD, EvalOptions, Evaluator,
                        GemmOp, Task, make_hw, resolve_auto_backend,
                        uniform_partition)
from repro.core import sweep
from repro.core.ga import (ENGINES, GAConfig, _move_units_vec,
                           _tournament_vec, run_ga)
from repro.core.workload import partition_domain

# Engine axis: (engine, fitness backend). "vectorized"+"jax" is the
# device-resident path (repro.core.ga_jax).
ENGINE_AXIS = [("python", "numpy"), ("vectorized", "numpy"),
               ("vectorized", "jax")]

OPTS = EvalOptions(redistribution=True, async_exec=True)


def divisible_task(n_ops=4, mx=4, nx=4, X=4, Y=4, R=16, C=16):
    """Task whose dims are exact multiples of (X*R)/(Y*C): the uniform
    partition sits exactly on the window center, so every genome an
    engine can reach stays strictly inside the Sec-6.2 window (no
    repair-residue escape hatch) — the strict-window property holds."""
    ops = [GemmOp("g0", M=mx * X * R, K=256, N=nx * Y * C)]
    for i in range(1, n_ops):
        ops.append(GemmOp(f"g{i}", M=mx * X * R, K=ops[-1].N,
                          N=nx * Y * C, chained=True, sync=(i % 3 == 0)))
    return Task(f"div{n_ops}_{mx}_{nx}", ops)


def assert_invariants(task, hw, cfg, result):
    part = result.partition
    part.validate(task)                       # exact per-op sums
    lo, hi = partition_domain(task, hw.X, hw.Y, hw.R, hw.C, cfg.slack)
    for i in range(len(task)):
        assert (part.Px[i] % hw.R == 0).all()
        assert (part.Px[i] >= lo[i, 0] * hw.R).all()
        assert (part.Px[i] <= hi[i, 0] * hw.R).all()
        assert (part.Py[i] % hw.C == 0).all()
        assert (part.Py[i] >= lo[i, 1] * hw.C).all()
        assert (part.Py[i] <= hi[i, 1] * hw.C).all()
    assert (part.collectors >= 0).all() and (part.collectors < hw.Y).all()
    # best-so-far history never regresses
    assert (np.diff(result.history) <= 1e-18).all()
    assert result.objective == pytest.approx(result.history[-1])
    assert result.evaluations == len(result.history) * cfg.population


@pytest.mark.parametrize("engine,backend", ENGINE_AXIS)
@pytest.mark.parametrize("seed", [0, 3])
def test_engine_invariants(engine, backend, seed):
    task = divisible_task()
    hw = make_hw("A", 4, "hbm", diagonal_links=True)
    cfg = GAConfig(generations=10, population=24, patience=10, seed=seed)
    r = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    assert_invariants(task, hw, cfg, r)


@pytest.mark.parametrize("engine,backend", ENGINE_AXIS)
def test_engine_elitism_beats_uniform(engine, backend):
    """Individual 0 is the LS-uniform partition and elitism keeps the
    best genome, so no engine may end worse than the uniform schedule."""
    task = divisible_task()
    hw = make_hw("A", 4, "hbm", diagonal_links=True)
    cfg = GAConfig(generations=10, population=24, patience=10, seed=1)
    base = Evaluator(task, hw, OPTS).evaluate(
        uniform_partition(task, hw.X, hw.Y))
    r = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    assert r.objective <= base.latency * (1 + 1e-12)


@pytest.mark.parametrize("engine,backend", ENGINE_AXIS)
def test_engine_deterministic_given_seed(engine, backend):
    task = divisible_task(n_ops=3)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=6, population=24, patience=6, seed=9)
    a = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    b = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    assert a.objective == b.objective
    np.testing.assert_array_equal(a.partition.Px, b.partition.Px)
    np.testing.assert_array_equal(a.history, b.history)


def test_unknown_engine_rejected():
    task = divisible_task(n_ops=2)
    with pytest.raises(ValueError, match="engine"):
        run_ga(task, make_hw("A", 2), engine="fortran")
    assert ENGINES == ("python", "vectorized")


@pytest.mark.parametrize("engine,backend", ENGINE_AXIS)
def test_zero_patience_runs_one_generation(engine, backend):
    """patience <= 0 means no flat-generation tolerance: every engine
    must still evaluate generation 0 (history/best exist) and stop right
    after it, never freeze an uninitialized genome."""
    task = divisible_task(n_ops=2)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=5, population=8, patience=0, seed=0)
    r = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    assert len(r.history) == 1
    assert r.evaluations == cfg.population
    assert_invariants(task, hw, cfg, r)


@pytest.mark.parametrize("engine,backend", ENGINE_AXIS)
def test_oversized_elite_clamped(engine, backend):
    """cfg.elite >= population must clamp (to population-1), identically
    on every engine, instead of crashing the offspring loop."""
    task = divisible_task(n_ops=2)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=3, population=4, elite=8, patience=3)
    r = run_ga(task, hw, "latency", OPTS, cfg, backend=backend,
               engine=engine)
    assert r.objective > 0
    assert r.evaluations == len(r.history) * cfg.population


def _median_objectives(task, hw, cfg_kw, engine, backend, seeds):
    objs = []
    for s in seeds:
        cfg = GAConfig(seed=s, **cfg_kw)
        objs.append(run_ga(task, hw, "latency", OPTS, cfg,
                           backend=backend, engine=engine).objective)
    return float(np.median(objs))


@pytest.mark.parametrize("wname", ["alexnet", "vit"])
def test_fixed_seed_quality_equivalence(wname):
    """The vectorized (device) engine must match the python engine's
    solution quality within 1% — median over 5 seeds (the engines draw
    from different RNGs, so point-wise trajectory equality is out of
    scope; DESIGN.md §10)."""
    from repro.graphs import WORKLOADS

    task = WORKLOADS[wname](batch=1)
    hw = make_hw("A", 4, "hbm", diagonal_links=True)
    cfg_kw = dict(generations=30, population=32, patience=30)
    seeds = range(5)
    py = _median_objectives(task, hw, cfg_kw, "python", "numpy", seeds)
    vec = _median_objectives(task, hw, cfg_kw, "vectorized", "jax", seeds)
    assert vec == pytest.approx(py, rel=0.01)


# ------------------------------------------------------------- auto backend
def test_resolve_auto_backend():
    assert AUTO_POPULATION_THRESHOLD == 1024
    assert resolve_auto_backend("auto", AUTO_POPULATION_THRESHOLD) == "jax"
    assert resolve_auto_backend("auto",
                                AUTO_POPULATION_THRESHOLD - 1) == "numpy"
    # concrete backends pass through untouched
    assert resolve_auto_backend("numpy", 10**6) == "numpy"
    assert resolve_auto_backend("jax", 1) == "jax"


def test_evaluator_auto_backend_matches_numpy():
    """backend="auto" resolves per evaluate_batch call by population
    size; small batches take the numpy path and must agree exactly."""
    task = divisible_task(n_ops=2)
    hw = make_hw("B", 4)
    part = uniform_partition(task, 4, 4)
    ev_auto = Evaluator(task, hw, OPTS, backend="auto")
    ev_np = Evaluator(task, hw, OPTS, backend="numpy")
    ra = ev_auto.evaluate(part)
    rn = ev_np.evaluate(part)
    assert ra.latency == rn.latency
    assert ra.energy == rn.energy


def test_ga_auto_backend_runs():
    task = divisible_task(n_ops=2)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=3, population=16, patience=3,
                   backend="auto", engine="vectorized")
    r = run_ga(task, hw, "latency", OPTS, cfg)
    assert r.objective > 0


# --------------------------------------------------------------- solve_grid
@pytest.fixture()
def _fresh_cache():
    sweep.clear_cache()
    yield
    sweep.clear_cache()


def test_solve_grid_matches_run_ga(_fresh_cache):
    """A point solved inside an island batch must equal the same point
    solved alone (per-island RNG depends only on cfg.seed) — the
    invariant that makes solver records cacheable."""
    task = divisible_task()
    other = divisible_task(mx=5)
    hw = make_hw("A", 4, "hbm", diagonal_links=True)
    cfg = GAConfig(generations=8, population=24, patience=8, seed=2)
    recs = sweep.solve_grid(
        [sweep.EvalPoint(task, hw, OPTS), sweep.EvalPoint(other, hw, OPTS)],
        "latency", cfg, cache=False)
    solo = run_ga(task, hw, "latency", OPTS, cfg, backend="jax",
                  engine="vectorized")
    assert recs[0].objective == solo.objective
    np.testing.assert_array_equal(recs[0].partition.Px, solo.partition.Px)
    np.testing.assert_array_equal(recs[0].history, solo.history)
    assert recs[0].evaluations == solo.evaluations
    for rec, t in zip(recs, (task, other)):
        assert_invariants(t, hw, cfg, rec)


def test_solve_grid_caches_solver_records(_fresh_cache):
    task = divisible_task(n_ops=3)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=4, population=16, patience=4, seed=0)
    pts = [sweep.EvalPoint(task, hw, OPTS)]
    a = sweep.solve_grid(pts, "latency", cfg)[0]
    assert sweep.cache_stats() == {"hits": 0, "misses": 1}
    b = sweep.solve_grid(pts, "latency", cfg)[0]
    assert sweep.cache_stats() == {"hits": 1, "misses": 1}
    assert a.objective == b.objective
    np.testing.assert_array_equal(a.partition.Px, b.partition.Px)
    # a different objective / config / backend is a different record
    sweep.solve_grid(pts, "edp", cfg)
    assert sweep.cache_stats()["misses"] == 2
    sweep.solve_grid(pts, "latency", GAConfig(generations=4, population=16,
                                              patience=4, seed=7))
    assert sweep.cache_stats()["misses"] == 3
    sweep.solve_grid(pts, "latency", cfg, backend="numpy")
    assert sweep.cache_stats()["misses"] == 4
    # cached records cross the boundary by value
    b.partition.Px[0, 0] += 1
    c = sweep.solve_grid(pts, "latency", cfg)[0]
    np.testing.assert_array_equal(a.partition.Px, c.partition.Px)


def test_solve_grid_backend_validation(_fresh_cache):
    """"auto" resolves by cfg.population before fingerprinting (sharing
    the cache with the concrete backend); anything unknown raises."""
    task = divisible_task(n_ops=2)
    pts = [sweep.EvalPoint(task, make_hw("A", 4), OPTS)]
    cfg = GAConfig(generations=2, population=8, patience=2)
    a = sweep.solve_grid(pts, "latency", cfg, backend="auto")[0]
    b = sweep.solve_grid(pts, "latency", cfg, backend="numpy")[0]
    assert sweep.cache_stats() == {"hits": 1, "misses": 1}  # shared record
    assert a.objective == b.objective
    with pytest.raises(ValueError, match="backend"):
        sweep.solve_grid(pts, "latency", cfg, backend="np")
    with pytest.raises(ValueError, match="backend"):
        sweep.eval_sweep(pts, backend="auto")


def test_solve_grid_numpy_backend(_fresh_cache):
    """run.py --backend numpy drives solve_grid too: per-point vectorized
    host engine, same record layout."""
    task = divisible_task(n_ops=2)
    hw = make_hw("A", 4)
    cfg = GAConfig(generations=4, population=16, patience=4)
    rec = sweep.solve_grid([sweep.EvalPoint(task, hw, OPTS)], "latency",
                           cfg, backend="numpy", cache=False)[0]
    ref = run_ga(task, hw, "latency", OPTS, cfg, backend="numpy",
                 engine="vectorized")
    assert rec.objective == ref.objective
    np.testing.assert_array_equal(rec.partition.Px, ref.partition.Px)


# ------------------------------------------- island initial populations
_RESULT_FIELDS = ("partition", "redist_mask", "objective", "history",
                  "evaluations")


def _assert_same_result(a, b):
    """Bitwise equality of two GAResults, field by field."""
    for f in _RESULT_FIELDS:
        x, y = getattr(a, f), getattr(b, f)
        if f == "partition":
            for k in ("Px", "Py", "collectors"):
                u, v = getattr(x, k), getattr(y, k)
                assert u.dtype == v.dtype and u.tobytes() == v.tobytes(), k
        elif isinstance(x, np.ndarray):
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), f
        else:
            assert x == y, f


def _recorded_builds(monkeypatch):
    """The host initial populations solve_islands builds, as built."""
    from repro.core import ga

    builds = []
    real = ga._random_population_vec

    def keep(rng, task, hw, cfg, pop):
        builds.append(real(rng, task, hw, cfg, pop))
        return builds[-1]

    monkeypatch.setattr(ga, "_random_population_vec", keep)
    return builds


# AlexNet on the 4x4 type-A package under the latency objective: the
# search moves off the uniform row within these few generations, so the
# results depend on every row of the initial population.
ISLAND_CFG = GAConfig(generations=8, population=16, patience=4, seed=4,
                      p_mutate_partition=0.1)
ISLAND_HWS = [make_hw("A", 4, "hbm", diagonal_links=d, bw_nop=b)
              for d, b in ((False, 15e9), (True, 30e9), (False, 60e9),
                           (True, 120e9))]


@pytest.mark.parametrize("batches,distinct", [
    # one task on one grid: every island shares one build
    ((1, 1, 1, 1), 1),
    # same op count, different M: each task gets its own population
    ((1, 2, 1, 2), 2),
])
def test_islands_share_init_bitwise(monkeypatch, batches, distinct):
    """Islands that differ only in links and NoP bandwidth share one host
    initial population, and each island's result is bitwise the result of
    the same island solved alone."""
    from repro.core.ga_jax import run_ga_jax, solve_islands
    from repro.graphs import WORKLOADS

    ts = [WORKLOADS["alexnet"](batch=b) for b in batches]
    solo = [run_ga_jax(t, h, "latency", OPTS, ISLAND_CFG)
            for t, h in zip(ts, ISLAND_HWS)]
    builds = _recorded_builds(monkeypatch)
    recs = solve_islands(ts, ISLAND_HWS, OPTS, "latency", ISLAND_CFG)
    assert len(builds) == distinct
    for rec, ref in zip(recs, solo):
        _assert_same_result(rec, ref)


def test_island_seeds_do_not_leak_through_shared_init(monkeypatch):
    """Two islands with one init key, only island 0 seeded: island 1 must
    read its unseeded solo result, and island 0 its seeded solo result,
    although both start from one shared build, which stays as built."""
    from repro.core import ga
    from repro.core.ga_jax import run_ga_jax, solve_islands
    from repro.graphs import WORKLOADS

    task = WORKLOADS["alexnet"](batch=1)
    hws = [ISLAND_HWS[1], ISLAND_HWS[0]]
    # single unit moves off the uniform row, each of which shortens its
    # latency on hws[0]; the 15 proposals combine four to seven of them
    moves = [(5, "Py", 0, 1), (6, "Py", 0, 1), (7, "Py", 0, 1),
             (3, "Px", 3, 0), (2, "Px", 3, 0), (3, "Py", 0, 3),
             (1, "Px", 3, 0)]
    props = []
    for mask in range(2 ** len(moves) - 1, 2 ** len(moves) - 16, -1):
        p = uniform_partition(task, 4, 4)
        for k, (i, axis, src, dst) in enumerate(moves):
            if mask >> k & 1:
                getattr(p, axis)[i, src] -= 16
                getattr(p, axis)[i, dst] += 16
        p.validate(task)
        props.append(p)
    seeded = [solve_islands([task], [h], OPTS, "latency", ISLAND_CFG,
                            seeds=[props])[0] for h in hws]
    cold = [run_ga_jax(task, h, "latency", OPTS, ISLAND_CFG) for h in hws]
    # the seeds change the search on both packages, so a seed row
    # reaching island 1 would show in its result
    for a, b in zip(seeded, cold):
        assert a.history.tobytes() != b.history.tobytes()

    fresh = ga._random_population_vec(
        np.random.default_rng(ISLAND_CFG.seed), task, hws[0], ISLAND_CFG,
        ISLAND_CFG.population)
    builds = _recorded_builds(monkeypatch)
    recs = solve_islands([task, task], hws, OPTS, "latency", ISLAND_CFG,
                         seeds=[props, []])
    _assert_same_result(recs[0], seeded[0])
    _assert_same_result(recs[1], cold[1])
    built, = builds
    for a, b in zip(built, fresh):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------- operator-level properties
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       X=st.sampled_from([2, 4, 6]),
       units=st.integers(min_value=2, max_value=8))
@settings(max_examples=25, deadline=None)
def test_move_units_vec_property(seed, X, units):
    """Population-wide unit moves preserve per-row sums and the window."""
    rng = np.random.default_rng(seed)
    n, P, R = 3, 8, 16
    lo = np.full(n, max(1, units - 2), dtype=np.int64)
    hi = np.full(n, units + 2, dtype=np.int64)
    P_ = np.full((P, n, X), units * R, dtype=np.int64)
    sums = P_.sum(axis=-1).copy()
    for _ in range(4):
        _move_units_vec(rng, P_, R, lo, hi,
                        rng.random((P, n)) < 0.7)
    np.testing.assert_array_equal(P_.sum(axis=-1), sums)
    assert (P_ % R == 0).all()
    assert (P_ >= lo[None, :, None] * R).all()
    assert (P_ <= hi[None, :, None] * R).all()


@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       k=st.integers(min_value=1, max_value=5))
@settings(max_examples=25, deadline=None)
def test_tournament_vec_property(seed, k):
    """Winners are valid indices and a tournament never returns a worse
    candidate than the best of its own draw (argmin semantics)."""
    rng = np.random.default_rng(seed)
    fit = rng.random(17)
    win = _tournament_vec(rng, fit, k, 32)
    assert win.shape == (32,)
    assert ((win >= 0) & (win < len(fit))).all()


@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_vectorized_engine_property(seed):
    """Randomized end-to-end invariants on the vectorized-numpy engine
    (the host reference the device port mirrors)."""
    rng = np.random.default_rng(seed)
    task = divisible_task(n_ops=int(rng.integers(1, 4)),
                          mx=int(rng.integers(2, 6)),
                          nx=int(rng.integers(2, 6)))
    hw = make_hw("A", 4, "hbm",
                 diagonal_links=bool(rng.integers(0, 2)))
    cfg = GAConfig(generations=4, population=12, patience=4,
                   seed=int(rng.integers(0, 2**31)))
    r = run_ga(task, hw, "latency", OPTS, cfg, backend="numpy",
               engine="vectorized")
    assert_invariants(task, hw, cfg, r)
