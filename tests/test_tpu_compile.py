"""Compile every solver engine and every Pallas kernel for a TPU v5e that
is described, not attached — what the chip's compiler would refuse
(float64 ops it cannot lower, unaligned tiles, too much VMEM) fails here
without chip time.

Each engine test drives the engine's own host entry point at a tiny
size up to the call of its jitted function and takes the arguments of
that call, so the compiled program is exactly the one the entry point
dispatches. The kernels compile at real widths.

The topology is described inside a fixture: only one process at a time
may load the TPU library, and every test worker imports this file.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import EvalOptions, GemmOp, Task, make_hw
from repro.core.x64 import x64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


TASK = Task("chain", [
    GemmOp("g0", M=512, K=256, N=512),
    GemmOp("g1", M=512, K=512, N=256, chained=True, sync=True),
    GemmOp("g2", M=512, K=256, N=512, chained=True),
])
#: Routed experts: two uneven grouped GEMMs (DESIGN.md §5).
_EXPERTS = (96, 0, 40, 200, 64, 8, 120, 56, 16, 88, 144, 24, 72, 0, 32, 64)
GROUPED = Task("experts", [
    GemmOp("router", M=512, K=256, N=16, sync=True),
    GemmOp("up", M=1024, K=256, N=512, group_sizes=_EXPERTS),
    GemmOp("down", M=1024, K=512, N=256, chained=True,
           group_sizes=_EXPERTS),
])
HW = make_hw("A", 4, "hbm")
OPTS = EvalOptions(redistribution=True, async_exec=True)


def _spec(x, sharding):
    if isinstance(x, (bool, int, float)):
        dt = {bool: jnp.bool_, int: jnp.int64, float: jnp.float64}[type(x)]
        return jax.ShapeDtypeStruct((), dt, weak_type=True,
                                    sharding=sharding)
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype, sharding=sharding)


class _Captured(Exception):
    """Raised in place of an engine's jitted call, carrying it."""

    def __init__(self, fn, args):
        super().__init__(fn)
        self.fn, self.args = fn, args


def _capture(monkeypatch, module, factory_name):
    """Make ``module.factory_name`` (an engine's cached jit factory)
    hand out functions that raise :class:`_Captured` instead of running:
    the entry point does all its host work, the CPU compiles nothing."""
    real = getattr(module, factory_name)

    def factory(*statics):
        fn = real(*statics)

        def captured(*args):
            raise _Captured(fn, args)
        return captured
    monkeypatch.setattr(module, factory_name, factory)


def _run_evaluator(congestion, task=TASK):
    from repro.core import Evaluator
    from repro.core.workload import uniform_partition

    opts = dataclasses.replace(OPTS, congestion=congestion)
    part = uniform_partition(task, HW.X, HW.Y)
    Evaluator(task, HW, opts, backend="jax").evaluate_batch(
        np.repeat(part.Px[None], 8, 0).astype(float),
        np.repeat(part.Py[None], 8, 0).astype(float),
        np.repeat(part.collectors[None], 8, 0), np.ones((8, len(task))))


def _run_ga(task=TASK):
    from repro.core import GAConfig
    from repro.core.ga_jax import run_ga_jax

    run_ga_jax(task, HW, "edp", OPTS,
               GAConfig(population=8, generations=2, patience=2))


def _run_miqp():
    from repro.core import MIQPConfig
    from repro.core.miqp_jax import solve_lattice_batch

    solve_lattice_batch([TASK], [HW], OPTS, "latency", MIQPConfig(
        engine="lattice", backend="jax", candidate_budget=64,
        eval_budget=64, descent_sweeps=0, refine_sweeps=0, pair_refine=0))


def _run_sgs():
    from repro.core.pipelining_jax import schedule_batch

    schedule_batch(np.random.default_rng(0).uniform(size=(2, 3, 3)), 2)


def _run_netsim():
    from repro.core import sweep
    from repro.core.netsim import MeshNet

    sweep.netsim_sweep([MeshNet(4, 4, 64.0, 128.0, [0, 3])], 1e6,
                       backend="jax", cache=False)


def _run_cosearch():
    from repro.core import CoSearchConfig, run_cosearch

    run_cosearch(TASK, HW, "edp", OPTS, CoSearchConfig(
        population=4, generations=2, patience=2, seed_fraction=0.0))


ENGINES = {
    "evaluator_regime": ("evaluator_jax", "population_fn",
                         lambda: _run_evaluator("regime")),
    "evaluator_flow": ("evaluator_jax", "population_fn",
                       lambda: _run_evaluator("flow")),
    "ga_chunk": ("ga_jax", "_chunk_fn", _run_ga),
    "evaluator_grouped_flow": ("evaluator_jax", "population_fn",
                               lambda: _run_evaluator("flow", GROUPED)),
    "ga_chunk_grouped": ("ga_jax", "_chunk_fn", lambda: _run_ga(GROUPED)),
    "miqp_scoring": ("evaluator_jax", "grid_fn", _run_miqp),
    "sgs": ("pipelining_jax", "_sched_fn", _run_sgs),
    "netsim": ("netsim_jax", "_batch_fn", _run_netsim),
    "cosearch_chunk": ("cosearch", "_chunk_fn", _run_cosearch),
}


@pytest.mark.parametrize("engine", list(ENGINES))
def test_engine_compiles_for_v5e(engine, one_chip, monkeypatch):
    import importlib

    from repro.core import sweep

    mod_name, factory, run = ENGINES[engine]
    module = importlib.import_module(f"repro.core.{mod_name}")
    sweep.clear_cache()
    _capture(monkeypatch, module, factory)
    with pytest.raises(_Captured) as got:
        run()
    with x64():
        specs = jax.tree.map(lambda a: _spec(a, one_chip), got.value.args)
        compiled = got.value.fn.lower(*specs).compile()
    assert compiled.memory_analysis() is not None


def _gemm(s):
    from repro.kernels.gemm.kernel import matmul

    a = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16, sharding=s)
    return matmul.lower(a, a)


def _flash(s):
    from repro.kernels.flash_attention.kernel import flash_attention

    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), jnp.bfloat16, sharding=s)
    kv = jax.ShapeDtypeStruct((1, 2048, 8, 128), jnp.bfloat16, sharding=s)
    return flash_attention.lower(q, kv, kv, causal=True)


def _wkv6(s):
    """rwkv6-3b: d_model 2560 in 40 heads of 64, WKV chunk 32."""
    from repro.kernels.rwkv6.kernel import wkv6

    x = jax.ShapeDtypeStruct((1, 2048, 40, 64), jnp.bfloat16, sharding=s)
    u = jax.ShapeDtypeStruct((40, 64), jnp.float32, sharding=s)
    return wkv6.lower(x, x, x, x, u, chunk=32)


def _ssm_scan(s):
    """zamba2-2.7b's Mamba2: d_inner 5120 in 80 heads of 64, state 64."""
    from repro.kernels.ssm_scan.kernel import ssm_scan

    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((1, 2048, 80, 64), jnp.bfloat16, sharding=s)
    dt = jax.ShapeDtypeStruct((1, 2048, 80), f32, sharding=s)
    a = jax.ShapeDtypeStruct((80,), f32, sharding=s)
    bc = jax.ShapeDtypeStruct((1, 2048, 1, 64), jnp.bfloat16, sharding=s)
    return ssm_scan.lower(x, dt, a, bc, bc, a, chunk=128)


@pytest.mark.parametrize("lower", [_gemm, _flash, _wkv6, _ssm_scan],
                         ids=["gemm", "flash_attention", "rwkv6",
                              "ssm_scan"])
def test_pallas_kernel_compiles_for_v5e(lower, one_chip):
    compiled = lower(one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()
