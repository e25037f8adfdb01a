"""The comparison that decides ``correct``.

Every answer the timed path produced (or a sample drawn from the seed)
is compared, after the window, with the configuration's plain reference
(:func:`reference_of`, interface in ``bench/reference/__init__.py``), by
the check of the call's kind (``bench/kinds``). Each comparison gives one
number, held against a limit that the traffic file states; the run is
correct when every number is at or under its limit. ``rel_err`` comes
from the repository's chip smoke run, unchanged in meaning.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from bench.harness import manifest

#: The reference a configuration without a ``reference`` key names.
DEFAULT_REFERENCE = "mcm"

#: Record fields that the evaluator's answer consists of.
EVAL_KEYS = ("latency", "energy", "edp", "t_in", "t_comp", "t_out",
             "E_sram", "E_mac", "E_mem", "E_nop")


def rel_err(got, want) -> float:
    """Largest relative error; a non-finite answer is an infinite one."""
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    den = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - want) / den, initial=0.0))


class Checks:
    """Numbers compared, each with its limit, in the order they were made."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.values: dict[str, float] = {}

    def worst(self, name: str, value: float) -> None:
        """Keep the largest value seen under ``name``."""
        self.values[name] = max(self.values.get(name, 0.0), float(value))

    def count(self, name: str, bad: bool = False) -> None:
        self.values[name] = self.values.get(name, 0) + int(bad)

    @property
    def correct(self) -> bool:
        return all(v <= self.limits[k] for k, v in self.values.items())

    def report(self) -> dict:
        return {k: {"value": v, "limit": self.limits[k]}
                for k, v in self.values.items()}

    def lines(self) -> list[str]:
        return [f"check {k}: {v!r} (limit {self.limits[k]!r})"
                for k, v in self.values.items()]


def reference_of(cfg: dict, root: Path):
    """The plain reference module of a configuration: the file
    ``bench/reference/<name>.py`` under ``root``, ``<name>`` being the
    configuration's ``reference`` key, or ``mcm`` without one. The only
    place that maps a configuration to its reference."""
    return manifest.module(root, "reference",
                           cfg.get("reference", DEFAULT_REFERENCE))


class Reference:
    """The plain reference for one configuration, one scorer per
    (package variant, congestion model); ``root`` is the checkout whose
    ``bench/reference`` holds it."""

    def __init__(self, cfg: dict, root: Path):
        self.cfg = cfg
        self.module = reference_of(cfg, root)
        self.ops = self.module.graph_ops(cfg["workload"])
        self._refs: dict[tuple, object] = {}

    def scorer(self, variant: dict | None = None,
               congestion: str | None = None):
        variant = variant or {}
        opts = dict(self.cfg["options"])
        if congestion is not None:
            opts["congestion"] = congestion
        key = (tuple(sorted(variant.items())), opts["congestion"])
        if key not in self._refs:
            self._refs[key] = self.module.Reference(
                self.ops, self.module.package(self.cfg, **variant), opts)
        return self._refs[key]
