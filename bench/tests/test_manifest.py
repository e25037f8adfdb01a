"""BENCHMARK.json against the contract's names and units, and every part
of a cell found by name, so that adding one is adding files and entries."""
import json
import re
import shutil

import pytest

from bench.harness import manifest
from bench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
M = manifest.load(ROOT)


def test_top_level_keys():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    assert (ROOT / M["command"][1]).is_file()
    assert all(not p.startswith("/") and ".." not in p for p in M["paths"])


def test_names_and_units():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in M[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len({e["name"] for e in M[k]}) == len(M[k])
    for m in M["end_to_end"] + M["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_every_metric_moves_what_its_cells_report():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    assert "setup_s" in e2e
    for m in M["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert manifest.applies(e2e[m["moves"]], w), (m["name"], w)


@pytest.mark.parametrize("w", [w["name"] for w in M["workloads"]])
def test_cell_parts_found_by_name(w):
    cell = manifest.cell(ROOT, w)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(manifest.reader(ROOT, m["name"]))
    assert set(cell.traffic["limits"]) and cell.chips in (1, 4)
    assert callable(cell.driver.drive)
    for f in KIND_API:
        assert callable(getattr(cell.kind, f)), f


KIND_API = ("call", "warm_calls", "prepare", "run", "check")


@pytest.mark.parametrize("path", sorted((ROOT / "bench/kinds").glob("[!_]*.py")),
                         ids=lambda p: p.stem)
def test_every_kind_has_its_functions(path):
    mod = manifest.module(ROOT, "kinds", path.stem)
    for f in KIND_API:
        assert callable(getattr(mod, f)), f


def test_configs_are_files_under_paths():
    for c in M["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert c["file"].startswith(tuple(p + "/" for p in M["paths"]))
        assert cfg.get("reduced", []) == c["reduced"]


def test_adding_a_cell_is_adding_files(tmp_path):
    """A new configuration, traffic mix and per-layer metric, as files and
    entries only, are found by name; no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/tests/data/alexnet.a4x4_hbm.json")
                     .read_text())
    cfg["name"] = "alexnet.a8x8_hbm"
    cfg["package"].update(X=8, Y=8)
    (tmp_path / "bench/configs/alexnet.a8x8_hbm.json").write_text(
        json.dumps(cfg))
    t = json.loads((ROOT / "bench/traffic/flow_eval.json").read_text())
    t["points"] = 64
    (tmp_path / "bench/traffic/flow_small.json").write_text(json.dumps(t))
    (tmp_path / "bench/metrics/calls_in_window.search.py").write_text(
        "def read(ctx):\n    return ctx['window'].attempted\n")
    m["configs"].append({"name": "alexnet.a8x8_hbm", "source": "x",
                         "file": "bench/configs/alexnet.a8x8_hbm.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "alex8.flow_small",
                           "config": "alexnet.a8x8_hbm",
                           "traffic": "flow_small", "chips": 1, "why": "x"})
    m["end_to_end"][0]["workloads"].append("alex8.flow_small")
    m["per_layer"].append({"name": "calls_in_window.search", "unit": "calls",
                           "better": "higher", "source": "program_counter",
                           "layer": "sweep driver",
                           "moves": "designs_per_s",
                           "workloads": ["alex8.flow_small"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.cell(tmp_path, "alex8.flow_small")
    assert cell.config["package"]["X"] == 8 and cell.traffic["points"] == 64
    assert [p["name"] for p in cell.per_layer] == ["calls_in_window.search"]

    class W:
        attempted = 3
    assert manifest.reader(tmp_path, "calls_in_window.search")(
        {"window": W}) == 3


ECHO = """
def call(traffic, cfg, seed, i):
    return {"i": i, "designs": traffic["per_call"]}


def warm_calls(traffic, cfg, seed):
    return [call(traffic, cfg, seed, -1)]


def prepare(system, c):
    return c["i"]


def run(system, c, args, cache=True):
    return [args] * c["designs"]


def check(checks, ref, traffic, w, seed):
    for a in w.answers:
        checks.count("echo_wrong", a["out"] != [a["call"]["i"]] * 3)
"""


def test_adding_a_traffic_kind_is_adding_files(tmp_path):
    """A kind of call that no existing file knows, with its traffic file,
    is found by name and run through the whole of a run: the driver, the
    kind's check, the metrics and the result line."""
    import jax

    from bench import run

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/kinds/echo.py").write_text(ECHO)
    (tmp_path / "bench/traffic/echo_mix.json").write_text(json.dumps(
        {"driver": "closed_loop", "kind": "echo", "per_call": 3,
         "limits": {"calls_failed": 0, "echo_wrong": 0}}))
    (tmp_path / "bench/configs/alexnet.a4x4_hbm.json").write_text(
        (ROOT / "bench/tests/data/alexnet.a4x4_hbm.json").read_text())
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "alexnet.a4x4_hbm", "source": "x",
                         "file": "bench/configs/alexnet.a4x4_hbm.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "alex4.echo", "config": "alexnet.a4x4_hbm",
                           "traffic": "echo_mix", "chips": 1, "why": "x"})
    m["end_to_end"][0]["workloads"].append("alex4.echo")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    cell = manifest.cell(tmp_path, "alex4.echo")
    out = run.run_cell(cell, 2**31 + 17, 0.05, False, jax.devices())
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"designs_per_s", "setup_s"}
    assert set(out["checks"]) == {"calls_failed", "echo_wrong"}


#: A plain reference that no existing file knows: a three-op chain whose
#: graph name ``mcm.GRAPHS`` lacks, on ``mcm``'s package and scorer; its
#: ``evaluate`` scales the latency by ``SCALE``.
CHAIN_REF = """
from bench.reference import mcm
from bench.reference.mcm import package  # noqa: F401

SCALE = {scale!r}


def graph_ops(workload):
    m, k = workload["m"], workload["k"]
    return [mcm.Op("in", m, k, k, epilogue=1),
            mcm.Op("mid", m, k, k, sync=True, chained=True),
            mcm.Op("out", m, k, 10, chained=True)]


class Reference(mcm.Reference):
    def evaluate(self, *args):
        out = super().evaluate(*args)
        return dict(out, latency=out["latency"] * SCALE)
"""


def _chain_task(m, k):
    """The program's side of ``CHAIN_REF``'s graph."""
    from repro.core.workload import GemmOp, Task

    return Task("chain3", [
        GemmOp("in", M=m, K=k, N=k, epilogue_flops_per_elem=1),
        GemmOp("mid", M=m, K=k, N=k, sync=True, chained=True),
        GemmOp("out", M=m, K=k, N=10, chained=True)])


def _architecture(tmp_path, monkeypatch, scale=1.0):
    """A checkout in ``tmp_path`` with a new architecture added as files: its
    reference module, its configuration naming it and a small flow-eval
    cell; the program's graph is patched in as a later PR would add it."""
    import repro.graphs

    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "bench/reference/chain3.py").write_text(
        CHAIN_REF.format(scale=scale))
    cfg = json.loads((ROOT / "bench/tests/data/alexnet.a4x4_hbm.json")
                     .read_text())
    cfg.update(name="chain3.a4x4_hbm", reference="chain3",
               workload={"graph": "chain3", "m": 96, "k": 48})
    (tmp_path / "bench/configs/chain3.a4x4_hbm.json").write_text(
        json.dumps(cfg))
    t = json.loads((ROOT / "bench/traffic/flow_eval.json").read_text())
    t["points"], t["check"]["sample"] = 6, 4
    (tmp_path / "bench/traffic/flow_tiny.json").write_text(json.dumps(t))
    m = json.loads((ROOT / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "chain3.a4x4_hbm", "source": "x",
                         "file": "bench/configs/chain3.a4x4_hbm.json",
                         "reduced": [], "why": "x"})
    m["workloads"].append({"name": "chain3.flow_tiny",
                           "config": "chain3.a4x4_hbm",
                           "traffic": "flow_tiny", "chips": 1, "why": "x"})
    m["end_to_end"][0]["workloads"].append("chain3.flow_tiny")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    monkeypatch.setitem(repro.graphs.WORKLOADS, "chain3", _chain_task)
    return manifest.cell(tmp_path, "chain3.flow_tiny")


@pytest.mark.parametrize("scale,correct", [(1.0, True), (1.0 + 1e-6, False)],
                         ids=["exact", "latency_skewed"])
def test_adding_an_architecture_is_adding_files(tmp_path, monkeypatch, scale,
                                                correct):
    """A graph that no existing reference knows is generated, run and
    checked through the reference its configuration names, and that
    reference alone decides ``correct``."""
    import warnings

    import jax

    from bench import run
    from bench.reference import mcm

    assert "chain3" not in mcm.GRAPHS
    cell = _architecture(tmp_path, monkeypatch, scale)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = run.run_cell(cell, 2**31 + 29, 0.05, False, jax.devices())
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["correct"] is correct, out["checks"]
    err = out["checks"]["eval_flow_rel_err"]["value"]
    assert (err < 1e-9) if correct else (err > 1e-7), err


@pytest.mark.parametrize("named", [False, True], ids=["default", "named"])
def test_reference_found_by_name(tmp_path, monkeypatch, named):
    """The configuration's ``reference`` key names the module; without it
    the module is ``mcm``."""
    from bench.harness import check

    cell = _architecture(tmp_path, monkeypatch)
    cfg = cell.config if named else json.loads(
        (ROOT / "bench/tests/data/alexnet.a4x4_hbm.json").read_text())
    assert ("reference" in cfg) is named
    mod = check.reference_of(cfg, tmp_path)
    want = "chain3" if named else check.DEFAULT_REFERENCE
    assert mod.__file__ == str(tmp_path.resolve() / "bench/reference"
                               / f"{want}.py")
    assert check.Reference(cfg, tmp_path).module is mod
