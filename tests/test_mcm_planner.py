"""Property + contract tests for the TPU layout planner
(``sharding/mcm_planner``): conservation of work under partitioning,
executable knobs, non-negative headroom, calibrated-profile plumbing, and
the plan → dryrun round-trip the validation gate relies on
(DESIGN.md §17)."""
import json

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import SHAPE_DEFS, get_config
from repro.core.evaluator import EvalOptions, Evaluator
from repro.core.workload import uniform_partition
from repro.sharding.mcm_planner import arch_to_task, plan, tpu_hw

ZOO = ("smollm-360m", "internlm2-20b", "rwkv6-3b", "mixtral-8x22b")
MESHES = ((1, 1), (2, 2), (4, 2), (4, 4))


def _total_partitioned_flops(task, X, Y):
    """FLOPs summed tile-by-tile over an X×Y uniform partition."""
    part = uniform_partition(task, X, Y)
    total = 0
    for i, op in enumerate(task.ops):
        total += 2 * int(part.Px[i].sum()) * op.K * int(part.Py[i].sum())
    return total


@settings(max_examples=24, deadline=None)
@given(st.sampled_from(ZOO), st.sampled_from(MESHES),
       st.integers(min_value=1, max_value=3))
def test_partition_conserves_flops_and_bytes(arch, mesh_shape, layers):
    """arch_to_task GEMM chains conserve FLOPs/bytes across mesh shapes:
    partitioning never creates or destroys work, and the task's totals
    don't depend on the grid it will be scored on."""
    cfg = get_config(arch)
    task = arch_to_task(cfg, 256, 8, layers=layers)
    X, Y = mesh_shape
    part = uniform_partition(task, X, Y)
    for i, op in enumerate(task.ops):
        assert int(part.Px[i].sum()) == op.M
        assert int(part.Py[i].sum()) == op.N
    assert _total_partitioned_flops(task, X, Y) == task.total_flops
    # byte totals come from the task alone — identical across grids
    ref = arch_to_task(cfg, 256, 8, layers=layers).arrays()
    for key in ("M", "K", "N", "w_scale"):
        assert np.array_equal(ref[key], task.arrays()[key])


def test_task_flops_linear_in_layers():
    for arch in ZOO:
        cfg = get_config(arch)
        f1 = arch_to_task(cfg, 128, 4, layers=1).total_flops
        f2 = arch_to_task(cfg, 128, 4, layers=2).total_flops
        f4 = arch_to_task(cfg, 128, 4, layers=4).total_flops
        # affine in L (the lm_head is the constant term)
        assert f4 - f2 == 2 * (f2 - f1)
        assert f2 > f1


def test_task_models_lm_head():
    cfg = get_config("smollm-360m")
    names = [op.name for op in arch_to_task(cfg, 128, 4, layers=1).ops]
    assert names[-1] == "lm_head"


@pytest.mark.parametrize("batch", [1, 2, 3, 6, 8])
def test_plan_knobs_always_executable(batch):
    """Redistribution mask ⊆ chained pairs, microbatch divides batch,
    headroom never below 1 (the planner only adopts a GA win)."""
    cfg = get_config("smollm-360m")
    pr = plan(cfg, (2, 2), 128, batch, layers=1, ga_budget=2)
    accum = pr.knobs["accum_steps"]
    assert batch % accum == 0
    hw = tpu_hw((2, 2))
    task = arch_to_task(cfg, 128, max(batch // 4, 1) * 4, layers=1)
    ev = Evaluator(task, hw, EvalOptions(redistribution=True))
    assert np.all(pr.redist_mask <= ev.chain_valid)
    assert pr.nonuniform_headroom >= 1.0
    assert pr.knobs["shard_residual"] == bool(pr.redist_mask.any())
    knobs = pr.to_dryrun_knobs()
    assert set(knobs) == {"shard_residual", "accum"}
    assert isinstance(knobs["shard_residual"], bool)
    assert isinstance(knobs["accum"], int)


def test_tpu_hw_profile_rescales_constants():
    from repro.kernels.calibrate import CalibratedHW
    prof = CalibratedHW(backend="cpu", flops_per_s=1e11, bytes_per_s=1e10,
                        byte_overhead=2.0)
    base = tpu_hw((4, 2))
    hw = tpu_hw((4, 2), profile=prof)
    assert hw.X == base.X and hw.Y == base.Y and hw.R == base.R
    assert hw.freq_hz == pytest.approx(1e11 / (2 * 128 * 128))
    assert hw.bw_mem == pytest.approx(5e9 * 8)     # ideal-byte basis × chips
    assert hw.bw_nop == pytest.approx(5e9 * prof.nop_frac)
    # plan() accepts the profile and still returns a valid result
    pr = plan(get_config("smollm-360m"), (2, 2), 128, 4, layers=1,
              ga_budget=2, profile=prof)
    assert pr.optimized_latency > 0


def test_plan_roundtrips_into_dryrun_artifact():
    """Acceptance criterion: a planner-chosen layout compiles through
    launch/dryrun — execute_plan lowers, compiles, and costs the plan's
    knobs and returns a JSON-serializable artifact record."""
    from repro.launch.dryrun import execute_plan
    from repro.launch.mesh import make_debug_mesh

    arch = "smollm-360m"
    cfg = get_config(arch, reduced=True)
    n = len(jax.devices())
    d = 2 if n % 2 == 0 and n >= 2 else 1
    mesh = make_debug_mesh(n)
    pr = plan(cfg, (d, n // d), 64, 8, layers=cfg.n_layers, ga_budget=2)
    shape = "__test_plan_roundtrip"
    SHAPE_DEFS[shape] = dict(seq_len=64, global_batch=8, kind="prefill")
    try:
        rec = execute_plan(pr, arch, shape, mesh, mesh_name="test",
                           cfg=cfg, serve_fsdp=("data",))
    finally:
        SHAPE_DEFS.pop(shape, None)
    assert rec["flops_per_device"] > 0
    assert rec["plan"]["knobs"]["shard_residual"] == \
        pr.knobs["shard_residual"]
    assert rec["plan"]["knobs"]["accum"] == pr.knobs["accum_steps"]
    assert rec["plan"]["redist_mask"] == [int(b) for b in pr.redist_mask]
    assert rec["plan"]["nonuniform_headroom"] >= 1.0
    json.dumps(rec)        # artifact-serializable end to end
