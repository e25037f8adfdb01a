"""numpy ↔ jax evaluator backend parity (DESIGN.md §8 contract).

The numpy implementation is the reference; the jax backend must agree on
latency/energy/EDP and the per-op breakdown within float64 round-off,
across randomized HWConfig / Task / Partition cases, and the GA must
produce identical trajectories under a fixed seed on both backends.
"""
import numpy as np
import pytest

from repro.core import (EvalOptions, Evaluator, GemmOp, Task, make_hw,
                        uniform_partition)
from repro.core.ga import GAConfig, run_ga
from repro.core.workload import clamp_partition_to_domain

RTOL = 1e-9

OPTION_SETS = [
    EvalOptions(),
    EvalOptions(redistribution=True),
    EvalOptions(async_exec=True),
    EvalOptions(redistribution=True, async_exec=True),
    EvalOptions(redistribution=True, async_exec=True,
                energy_mode="per_chiplet"),
]


def random_task(rng, n_ops=4):
    ops = []
    prev_n = None
    for i in range(n_ops):
        m = int(rng.integers(4, 80)) * 16
        k = prev_n if (prev_n and rng.random() < 0.5) \
            else int(rng.integers(2, 40)) * 16
        n = int(rng.integers(4, 80)) * 16
        ops.append(GemmOp(
            f"g{i}", M=m, K=k, N=n,
            sync=bool(rng.random() < 0.3),
            chained=bool(i > 0 and rng.random() < 0.6),
            epilogue_flops_per_elem=int(rng.integers(0, 4)),
            weight_bytes_scale=float(rng.choice([0.25, 0.5, 1.0])),
        ))
        prev_n = n
    return Task("rand", ops)


def random_hw(rng):
    t = rng.choice(list("ABCD"))
    g = int(rng.choice([2, 4, 6]))
    mem = rng.choice(["hbm", "dram"])
    return make_hw(str(t), g, str(mem),
                   diagonal_links=bool(rng.random() < 0.5))


def random_population(rng, task, hw, pop=6):
    X, Y = hw.X, hw.Y
    base = uniform_partition(task, X, Y)
    parts = []
    for _ in range(pop):
        p = base.copy()
        p.Px = p.Px + rng.integers(-2, 3, p.Px.shape) * hw.R
        p.Px = np.maximum(p.Px, 0)
        p = clamp_partition_to_domain(p, task, X, Y, hw.R, hw.C)
        p.collectors = rng.integers(0, Y, len(task))
        parts.append(p)
    Px = np.stack([p.Px for p in parts]).astype(np.float64)
    Py = np.stack([p.Py for p in parts]).astype(np.float64)
    co = np.stack([p.collectors for p in parts])
    rd = (rng.random((pop, len(task))) < 0.5).astype(np.float64)
    return Px, Py, co, rd


@pytest.mark.parametrize("seed", range(8))
def test_randomized_batch_parity(seed):
    rng = np.random.default_rng(seed)
    task = random_task(rng, n_ops=int(rng.integers(1, 6)))
    hw = random_hw(rng)
    opts = OPTION_SETS[seed % len(OPTION_SETS)]
    evn = Evaluator(task, hw, opts, backend="numpy")
    evj = Evaluator(task, hw, opts, backend="jax")
    Px, Py, co, rd = random_population(rng, task, hw)
    a = evn.evaluate_batch(Px, Py, co, rd)
    b = evj.evaluate_batch(Px, Py, co, rd)
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], rtol=RTOL, err_msg=k)


@pytest.mark.parametrize("t", list("ABCD"))
def test_single_eval_parity_all_types(t):
    task = Task("chain", [
        GemmOp("g0", M=512, K=256, N=512),
        GemmOp("g1", M=512, K=512, N=256, chained=True, sync=True),
        GemmOp("g2", M=512, K=256, N=512, chained=True),
    ])
    hw = make_hw(t, 4, "hbm", diagonal_links=True)
    part = uniform_partition(task, 4, 4)
    rd = np.array([True, True, False])
    for opts in OPTION_SETS:
        rn = Evaluator(task, hw, opts, backend="numpy").evaluate(part, rd)
        rj = Evaluator(task, hw, opts, backend="jax").evaluate(part, rd)
        assert rj.latency == pytest.approx(rn.latency, rel=RTOL)
        assert rj.energy == pytest.approx(rn.energy, rel=RTOL)
        assert rj.edp == pytest.approx(rn.edp, rel=RTOL)
        np.testing.assert_allclose(rj.t_in, rn.t_in, rtol=RTOL)
        np.testing.assert_allclose(rj.t_comp, rn.t_comp, rtol=RTOL)
        np.testing.assert_allclose(rj.t_out, rn.t_out, rtol=RTOL)


def test_ga_identical_trajectories():
    """Fixed seed ⇒ the GA visits the same genomes on both backends.

    Per-platform guarantee (DESIGN.md §8): holds on CPU where XLA's
    float64 reductions track numpy to ≤1 ulp with no near-tie flips; on
    a platform where this fails with tiny fitness deltas, weaken to the
    rtol=1e-9 value contract rather than loosening it here for CPU.
    """
    from repro.graphs import WORKLOADS

    task = WORKLOADS["alexnet"](batch=1)
    hw = make_hw("A", 4, "hbm", diagonal_links=True)
    cfg = GAConfig(generations=12, population=32, seed=11)
    rn = run_ga(task, hw, "latency", cfg=cfg, backend="numpy")
    rj = run_ga(task, hw, "latency", cfg=cfg, backend="jax")
    assert rn.evaluations == rj.evaluations
    assert len(rn.history) == len(rj.history)
    np.testing.assert_allclose(rn.history, rj.history, rtol=RTOL)
    assert rj.objective == pytest.approx(rn.objective, rel=RTOL)
    np.testing.assert_array_equal(rn.partition.Px, rj.partition.Px)
    np.testing.assert_array_equal(rn.partition.Py, rj.partition.Py)
    np.testing.assert_array_equal(rn.partition.collectors,
                                  rj.partition.collectors)
    np.testing.assert_array_equal(rn.redist_mask, rj.redist_mask)


def test_backend_validation():
    task = Task("one", [GemmOp("g", M=64, K=64, N=64)])
    with pytest.raises(ValueError):
        Evaluator(task, make_hw("A", 2), backend="tpu")


def test_objective_batch_jax():
    task = Task("one", [GemmOp("g", M=256, K=128, N=256)])
    hw = make_hw("B", 4)
    part = uniform_partition(task, 4, 4)
    for obj in ("latency", "energy", "edp"):
        a = Evaluator(task, hw, backend="numpy").objective_batch(
            part.Px[None].astype(float), part.Py[None].astype(float),
            part.collectors[None], np.zeros((1, 1)), obj)
        b = Evaluator(task, hw, backend="jax").objective_batch(
            part.Px[None].astype(float), part.Py[None].astype(float),
            part.collectors[None], np.zeros((1, 1)), obj)
        np.testing.assert_allclose(a, b, rtol=RTOL)


def test_x64_does_not_leak():
    """The jax backend's x64 scope must not flip global jax defaults."""
    import jax.numpy as jnp

    task = Task("one", [GemmOp("g", M=256, K=128, N=256)])
    hw = make_hw("A", 4)
    Evaluator(task, hw, backend="jax").evaluate(
        uniform_partition(task, 4, 4))
    assert jnp.asarray(1.0).dtype == jnp.float32


def _entry_points():
    """One small call of every jitted engine's public entry point."""
    from repro.core import CoSearchConfig, MIQPConfig, run_cosearch, sweep
    from repro.core.ga_jax import run_ga_jax
    from repro.core.miqp_jax import solve_lattice_batch
    from repro.core.netsim import MeshNet
    from repro.core.pipelining_jax import schedule_batch

    task = Task("chain", [GemmOp("g0", M=256, K=128, N=256),
                          GemmOp("g1", M=256, K=256, N=128, chained=True)])
    hw = make_hw("A", 2)
    opts = EvalOptions(redistribution=True)
    pts = [sweep.EvalPoint(task, hw, opts)] * 2
    return {
        "evaluator": lambda: Evaluator(task, hw, backend="jax").evaluate(
            uniform_partition(task, 2, 2)),
        "eval_sweep": lambda: sweep.eval_sweep(pts, cache=False,
                                               devices="single"),
        "eval_sweep_sharded": lambda: sweep.eval_sweep(
            pts, cache=False, devices="sharded"),
        "netsim": lambda: sweep.netsim_sweep(
            [MeshNet(2, 2, 64.0, 128.0, [0])], 1e6, cache=False),
        "ga": lambda: run_ga_jax(task, hw, "latency", opts, GAConfig(
            population=4, generations=2, patience=2)),
        "miqp": lambda: solve_lattice_batch([task], [hw], opts, "latency",
                                            MIQPConfig(backend="jax")),
        "sgs": lambda: schedule_batch(np.ones((1, 2, 3)), 2),
        "cosearch": lambda: run_cosearch(task, hw, "edp", opts,
                                         CoSearchConfig(
                                             population=4, generations=2,
                                             seed_steps=2, seed_starts=2)),
    }


@pytest.mark.parametrize("engine", ["evaluator", "eval_sweep",
                                    "eval_sweep_sharded", "netsim", "ga",
                                    "miqp", "sgs", "cosearch"])
def test_engine_leaves_x64_off(engine):
    """Each engine computes in float64 inside its own scope and leaves
    the global setting (float32 default) as it found it."""
    import jax
    import jax.numpy as jnp

    _entry_points()[engine]()
    assert not jax.config.jax_enable_x64
    assert jnp.asarray(1.0).dtype == jnp.float32


@pytest.mark.parametrize("shape,axis", [((257,), -1), ((6, 16), -1),
                                        ((16, 5), 0), ((0,), -1)])
def test_cumsum_seq_bitwise_equals_numpy(shape, axis):
    """The sequential scan adds in np.cumsum's order: bitwise equal on
    random float64 input spanning many magnitudes (where a different
    summation order would round differently), signed zeros included."""
    from repro.core.x64 import cumsum_seq, x64

    rng = np.random.default_rng(sum(shape) + 10 * abs(axis))
    x = rng.normal(size=shape) * 10.0 ** rng.integers(-8, 9, size=shape)
    if x.size:
        x.flat[0] = -0.0
    want = np.cumsum(x, axis=axis)
    with x64():
        got = np.asarray(cumsum_seq(x, axis=axis))
    assert got.dtype == np.float64
    assert got.tobytes() == want.tobytes()
