"""The DeepSeek-V2 configuration and its plain reference: the reference
against the program at the published widths, its grouped term against a
loop over every row, the expert counts reproduced from their stated draw,
and the GA cell at a CPU size, correct when sound and not correct under
the float32 control or when the routed experts are flattened onto M as
one op (every chiplet row fetching and tiling all 160 experts)."""
import contextlib
import copy
import dataclasses
import importlib.util
import json
import subprocess
import sys

import numpy as np
import pytest

from bench.harness import check, faults, manifest
from bench.harness import generate as gen
from bench.tests.conftest import ROOT

W = "dsv2.ga_islands"
CFG_FILE = ROOT / "bench/configs/deepseek_v2.a16x16_hbm.json"
CFG = json.loads(CFG_FILE.read_text())


def row_by_row(ops, pk, Px):
    """Per grouped op: experts each chiplet row needs and its row tiles,
    walking the op's rows one at a time."""
    out = {}
    for i, op in enumerate(ops):
        if not op.groups:
            continue
        rows = np.zeros((pk.X, len(op.groups)), dtype=int)
        chiplet_row = np.repeat(np.arange(pk.X), Px[i].astype(int))
        expert = np.repeat(np.arange(len(op.groups)), op.groups)
        for x, g in zip(chiplet_row, expert):
            rows[x, g] += 1
        out[i] = ((rows > 0).sum(axis=1), (-(-rows // pk.R)).sum(axis=1))
    return out


@pytest.mark.parametrize("mcm_type", ["A", "B"])
def test_grouped_term_matches_row_by_row(mcm_type):
    ref_mod = check.reference_of(CFG, ROOT)
    ops = ref_mod.graph_ops(CFG["workload"])
    ref = ref_mod.Reference(ops, ref_mod.package(CFG, mcm_type=mcm_type),
                            CFG["options"])
    Px, _, _ = gen.partitions(gen.rng(5, 2), ops, ref.pk, 2)
    for k in range(2):
        rows, fetch, held, tiles = ref.grouped(Px[k].astype(np.float64))
        want = row_by_row(ops, ref.pk, Px[k])
        assert len(want) == 8
        for i, (t, row_tiles) in want.items():
            np.testing.assert_array_equal(rows[i], t)
            np.testing.assert_array_equal(tiles[i], row_tiles)
            assert held[i] == t.sum()
            # an entrance of type B serves one chiplet row
            if mcm_type == "B":
                np.testing.assert_array_equal(
                    fetch[i], [t[x] for x in ref.pk.row_mask.argmax(axis=1)])
            else:
                assert fetch[i] == [sum(g > 0 for g in ops[i].groups)]


@pytest.mark.parametrize("variant,congestion", [
    ({}, "regime"), ({}, "flow"),
    ({"mcm_type": "B", "bw_nop": 15e9}, "regime"),
    ({"diagonal_links": True, "bw_mem": 250e9}, "flow")])
def test_reference_matches_program(variant, congestion):
    from bench.harness import sut
    from repro.core import Evaluator
    from repro.core.hw import MCMType
    from repro.core.workload import Partition

    system = sut.System(CFG)
    hw = system.hw_for({k: MCMType(v) if k == "mcm_type" else v
                        for k, v in variant.items()})
    opts = dataclasses.replace(system.options, congestion=congestion)
    ev = Evaluator(system.task, hw, opts, backend="numpy")
    mod = check.reference_of(CFG, ROOT)
    ops = mod.graph_ops(CFG["workload"])
    assert [(o.name, o.M, o.K, o.N, o.groups) for o in ops] == [
        (o.name, o.M, o.K, o.N, o.group_sizes) for o in system.task.ops]
    ref = mod.Reference(ops, mod.package(CFG, **variant),
                        dict(CFG["options"], congestion=congestion))
    Px, Py, co = gen.partitions(gen.rng(7, len(variant)), ops, ref.pk, 2)
    for k in range(2):
        mask = np.arange(len(ops)) % 3 != 0
        got = ev.evaluate_batch(Px[k][None].astype(np.float64),
                                Py[k][None].astype(np.float64),
                                co[k][None], ref.redist_of(mask)[None])
        want = ref.evaluate(Px[k], Py[k], co[k], ref.redist_of(mask))
        for key in check.EVAL_KEYS:
            assert check.rel_err(got[key][0], want[key]) <= 1e-12, key
        assert ev.evaluate(Partition(Px[k], Py[k], co[k]), mask).latency \
            == got["latency"][0]


def test_reference_widths_are_the_published_config():
    mod = check.reference_of(CFG, ROOT)
    for key, value in mod.PUBLISHED.items():
        assert CFG[key] == value, key
    assert CFG["num_hidden_layers"] == (CFG["workload"]["layers_dense"]
                                        + CFG["workload"]["layers_moe"])
    assert CFG["first_k_dense_replace"] == CFG["workload"]["layers_dense"]
    assert len(mod.graph_ops(CFG["workload"])) == 53


def test_expert_tokens_reproduce_their_draw():
    out = subprocess.run(
        [sys.executable, str(ROOT / "bench/tools/expert_tokens.py"),
         "--check", str(CFG_FILE)], capture_output=True, text=True)
    assert out.returncode == 0, out.stdout + out.stderr
    counts = np.array(CFG["workload"]["expert_tokens"])
    assert counts.shape == (4, 160)
    assert (counts.sum(axis=1) == 6 * CFG["workload"]["tokens"]).all()


# ------------------------------------------------ the cell at a CPU size
def tiny_cell() -> manifest.Cell:
    """The GA cell on one 64-token chunk, one MoE layer, a 4x4 package."""
    spec = importlib.util.spec_from_file_location(
        "expert_tokens", ROOT / "bench/tools/expert_tokens.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    c = manifest.cell(ROOT, W)
    c.config = copy.deepcopy(c.config)
    c.config["workload"].update(tokens=64, layers_moe=1,
                                expert_tokens=tool.draw(64, 1))
    c.config["package"].update(X=4, Y=4)
    c.traffic["solver"].update(population=16, generations=30, patience=30)
    return c


@contextlib.contextmanager
def flattened():
    """Every routed-expert op priced as today's flattened op: each chiplet
    row fetches and tiles every expert (t_x = number of experts)."""
    from repro.core import evaluator_jax as ev
    from repro.core import ga_jax

    orig = ev._group_terms

    def terms(c, Px):
        out = dict(orig(c, Px))
        G = float(c["g_off"].shape[-1] - 1)
        ng, X = c["g_off"].shape[0], Px.shape[1]
        full = ev.jnp.full((ng, X), G)
        out.update(rows=ev._by_op(c, full, 1.0),
                   fetch=ev._by_op(c, full[:, :1], 1.0),
                   held=ev._by_op(c, full.sum(axis=-1), float(X)),
                   tiles=ev.jnp.ceil(Px / c["R"]))
        return out

    def clear():
        for fn in (ga_jax._chunk_fn, ga_jax._chunk_inner, ev.population_fn,
                   ev.grid_fn, ev._grid_inner):
            fn.cache_clear()
    ev._group_terms = terms
    clear()
    try:
        yield
    finally:
        ev._group_terms = orig
        clear()


def run_tiny(whole=None) -> dict:
    import warnings

    import jax

    from bench import run as bench_run

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with whole or contextlib.nullcontext():
            return bench_run.run_cell(tiny_cell(), 3_000_000_016, 0.5, False,
                                      jax.devices())


def test_sound_run_is_correct():
    out = run_tiny()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_flattened_experts_are_not_correct():
    out = run_tiny(flattened())
    assert not out["correct"], out["checks"]
    assert out["checks"]["solve_rescore_rel_err"]["value"] > 1e-9


def test_control_is_not_correct():
    out = run_tiny(faults.float32())
    assert not out["correct"], out["checks"]
