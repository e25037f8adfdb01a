"""Finds a cell's parts by name from ``BENCHMARK.json``.

* a configuration is the file its entry names, ``bench/configs/<name>.json``;
* a plain reference is the module ``bench/reference/<name>.py`` that the
  configuration's ``reference`` key names, ``mcm`` without it
  (``bench.harness.check.reference_of``; see ``bench/reference/__init__.py``);
* a traffic mix is ``bench/traffic/<name>.json``, a file of parameters
  that names its ``kind`` and its ``driver``;
* a kind is the module ``bench/kinds/<kind>.py``: what one call of that
  kind asks (its generator), how the program is called for it, and how
  its answers are checked (see ``bench/kinds/__init__.py``);
* a driver is the module ``bench/drivers/<driver>.py`` with a function
  ``drive(...)`` that runs set-up and one measured window;
* a metric, end-to-end or per-layer, is the module
  ``bench/metrics/<name>.py`` with a function ``read(ctx)``.

Adding one is adding its file and its entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    root: Path = Path(".")

    @property
    def devices(self) -> str:
        return self.traffic.get("devices", "single")

    @property
    def kind(self):
        return module(self.root, "kinds", self.traffic["kind"])

    @property
    def driver(self):
        return module(self.root, "drivers", self.traffic["driver"])


def load(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def applies(spec: dict, cell: str, reported=None) -> bool:
    """Whether a metric is read in a cell: the cells its ``workloads``
    lists, else every cell that reports the metric it moves."""
    if "workloads" in spec:
        return cell in spec["workloads"]
    return reported is None or spec["moves"] in reported


def cell(root: Path, name: str) -> Cell:
    m = load(root)
    wl = {w["name"]: w for w in m["workloads"]}
    if name not in wl:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = wl[name]
    cfg = {c["name"]: c for c in m["configs"]}[w["config"]]
    config = json.loads((Path(root) / cfg["file"]).read_text())
    traffic = json.loads((Path(root) / "bench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
    e2e = [s for s in m["end_to_end"] if applies(s, name)]
    reported = {s["name"] for s in e2e}
    per = [s for s in m["per_layer"] if applies(s, name, reported)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per,
                Path(root))


_MODULES: dict[Path, object] = {}


def module(root: Path, subdir: str, name: str):
    """The module ``bench/<subdir>/<name>.py`` under ``root``, loaded once
    from its file (names may hold dots, which import paths cannot) and
    entered in ``sys.modules``, which dataclasses look up."""
    path = (Path(root) / "bench" / subdir / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise SystemExit(f"bench: no {subdir} module {name!r} ({path})")
        mod_name = "bench_{}_{}".format(
            subdir, name.replace(".", "_").replace("-", "_"))
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[mod_name] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(root: Path, name: str):
    """The ``read`` function of metric ``name``."""
    return module(root, "metrics", name).read
