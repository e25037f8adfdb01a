"""The system under test, as the benchmark drives it.

It turns the generator's plain data into the program's own inputs, and
what the program returns back into plain data for the checks; the kinds
(``bench/kinds``) call the program's entry points through it, always with
``backend="jax"``. Nothing here computes a result.
"""
from __future__ import annotations

import dataclasses

import numpy as np


class System:
    """The program, built for one configuration."""

    def __init__(self, cfg: dict, devices: str = "single"):
        from repro.core import EvalOptions, HWConfig, sweep
        from repro.core.hw import MCMType
        from repro.graphs import WORKLOADS

        self.sweep = sweep
        wl = dict(cfg["workload"])
        self.task = WORKLOADS[wl.pop("graph")](**wl)
        pk = {k: v for k, v in cfg["package"].items() if k != "memory"}
        pk["mcm_type"] = MCMType(pk["mcm_type"])
        self.hw = HWConfig(**pk)
        self.options = EvalOptions(devices=devices, **cfg["options"])
        self.devices = devices
        self._hws: dict[tuple, object] = {}

    # ------------------------------------------------------------ inputs
    def hw_for(self, variant: dict):
        """The package with the traffic's fields changed; one object per
        variant, so its topology is built once."""
        key = tuple(sorted(variant.items()))
        if key not in self._hws:
            self._hws[key] = dataclasses.replace(self.hw, **variant)
        return self._hws[key]

    def ga_config(self, solver: dict):
        from repro.core import GAConfig

        return GAConfig(backend="jax", devices=self.devices, **solver)

    def eval_point(self, Px, Py, co, congestion: str):
        from repro.core.sweep import EvalPoint
        from repro.core.workload import Partition

        opts = dataclasses.replace(self.options, congestion=congestion)
        return EvalPoint(self.task, self.hw, opts,
                         Partition(np.asarray(Px), np.asarray(Py),
                                   np.asarray(co)))

    def solve_point(self, variant: dict):
        from repro.core.sweep import EvalPoint

        return EvalPoint(self.task, self.hw_for(variant), self.options)


def plain(result) -> dict:
    """A result of the program as plain data for the checks."""
    if isinstance(result, dict):
        return {k: result[k] for k in ("latency", "energy", "edp", "t_in",
                                       "t_comp", "t_out", "E_sram", "E_mac",
                                       "E_mem", "E_nop")}
    return {"Px": result.partition.Px, "Py": result.partition.Py,
            "collectors": result.partition.collectors,
            "redist_mask": result.redist_mask,
            "objective": result.objective, "history": result.history,
            "evaluations": result.evaluations}
