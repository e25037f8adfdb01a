"""Cells of the benchmark cut to a size the CPU test run can hold: the
AlexNet 4x4 test configuration, a GA of 8 islands x 16 x 30 generations,
6 flow points."""
from __future__ import annotations

import json

from bench.harness import manifest
from bench.tests.conftest import ROOT

ALEX = ROOT / "bench/tests/data/alexnet.a4x4_hbm.json"
SECONDS = {"vit16.ga_islands": 0.5, "vit16.flow_eval": 0.5}


def cell(name: str) -> manifest.Cell:
    c = manifest.cell(ROOT, name)
    c.config = json.loads(ALEX.read_text())
    t = c.traffic
    if "solver" in t:
        t["solver"].update(population=16, generations=30, patience=30)
    if "points" in t:
        t["points"], t["check"]["sample"] = 6, 4
    return c


def run(name: str, seed: int = 1234567, timed_patch=None, whole=None):
    """One run of the tiny cell on the CPU, past the harness's look for a
    chip; ``whole`` is held for the whole run, ``timed_patch`` for the
    window only."""
    import contextlib
    import warnings

    import jax

    from bench import run as bench_run

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with whole or contextlib.nullcontext():
            return bench_run.run_cell(cell(name), seed, SECONDS[name], False,
                                      jax.devices(), timed_patch=timed_patch)
