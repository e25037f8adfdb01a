"""The benchmark's plain references agree with the program's own numpy
reference (each is a copy of its equations, kept where no later change to
the program reaches it), and import nothing of the program."""
import ast
import dataclasses
import json

import numpy as np
import pytest

from bench.harness import check, manifest
from bench.harness import generate as gen
from bench.reference import mcm
from bench.tests.conftest import ROOT

ALEX = json.loads((ROOT / "bench/tests/data/alexnet.a4x4_hbm.json").read_text())
SMALL_VIT = dict(ALEX, workload={"graph": "vit", "batch": 1, "depth": 2,
                                 "d": 64, "heads": 4, "mlp_ratio": 4,
                                 "tokens": 17, "patch_dim": 48})
#: The test fixtures at small sizes, then every configuration the
#: benchmark runs, at its own size, each through its own reference.
CONFIGS = {"alexnet": ALEX, "vit": SMALL_VIT} | {
    c["name"]: json.loads((ROOT / c["file"]).read_text())
    for c in manifest.load(ROOT)["configs"]}


@pytest.mark.parametrize("cfg", list(CONFIGS.values()), ids=list(CONFIGS))
@pytest.mark.parametrize("congestion", ["regime", "flow"])
@pytest.mark.parametrize("variant", [{}, {"diagonal_links": True,
                                          "bw_nop": 15e9}])
def test_evaluator_matches_program(cfg, congestion, variant):
    from bench.harness import sut
    from repro.core import Evaluator
    from repro.core.workload import Partition

    system = sut.System(cfg)
    hw = system.hw_for(variant)
    opts = dataclasses.replace(system.options, congestion=congestion)
    ev = Evaluator(system.task, hw, opts, backend="numpy")
    mod = check.reference_of(cfg, ROOT)
    ops = mod.graph_ops(cfg["workload"])
    ref = mod.Reference(ops, mod.package(cfg, **variant),
                        dict(cfg["options"], congestion=congestion))
    Px, Py, co = gen.partitions(gen.rng(3, 1), ops, ref.pk, 3)
    for k in range(3):
        mask = np.arange(len(ops)) % 2 == 0
        got = ev.evaluate(Partition(Px[k], Py[k], co[k]), mask)
        want = ref.evaluate(Px[k], Py[k], co[k], ref.redist_of(mask))
        for key in ("latency", "energy", "edp", "t_in", "t_comp", "t_out"):
            np.testing.assert_allclose(getattr(got, key), want[key],
                                       rtol=1e-12)


def test_check_partition_rejects():
    ops = mcm.graph_ops(ALEX["workload"])
    ref = mcm.Reference(ops, mcm.package(ALEX), ALEX["options"])
    Px, Py = gen.base_partition(ops, ref.pk)
    co = np.zeros(len(ops), dtype=int)
    assert ref.check_partition(Px, Py, co) is None
    bad = Px.copy()
    bad[0, 0] += 1
    assert ref.check_partition(bad, Py, co) is not None
    assert ref.check_partition(Px, Py, co + 4) is not None


def _imported(path) -> set[str]:
    """The top-level package of every module a file imports."""
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    return names


@pytest.mark.parametrize("path", sorted((ROOT / "bench/reference").glob("*.py")),
                         ids=lambda p: p.stem)
def test_reference_imports_nothing_of_the_program(path):
    """Neither by an import statement nor by importing at run time."""
    assert not {"repro", "importlib"} & _imported(path), path
    assert "__import__" not in path.read_text(), path
