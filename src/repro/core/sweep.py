"""Batched design-space sweep engine (DESIGN.md §9).

Design-space exploration hammers the analytical evaluator across
(HWConfig × Task × EvalOptions) grids — Figs. 8–13 alone cover four
packaging types × four workloads × three solvers × multiple grid sizes.
This module turns those hand-rolled Python loops into:

  * :func:`grid` — generic named-axis cartesian product (any axes, not
    just eval triples; ``benchmarks/fig3_motivation.py`` builds its
    netsim grid with it too);
  * :func:`run_grid` — the timed per-point driver for work that stays
    per-point (the HiGHS ``engine="milp"`` path and other external
    solvers), with an optional per-point progress line;
  * :class:`PipelinePoint` / :func:`pipeline_sweep` — *batched* RCPSP
    pipelining (DESIGN.md §13): same-(n_ops, batch) points schedule
    through one compiled ``pipelining_jax.schedule_batch`` call, with
    method-tagged cached records (the ``engine="milp"`` refinement stays
    per-point);
  * :func:`netsim_sweep` — *batched* flow simulation (DESIGN.md §11):
    same-mesh-shape nets run through one compiled
    ``netsim_jax.simulate_pull_batch`` call, with cached records;
  * :class:`EvalPoint` / :func:`eval_sweep` — *batched* evaluation: all
    points whose shape signature (n_ops, X, Y, n_entrances) and static
    options match are stacked along a grid axis and evaluated by ONE
    ``jax.jit`` call (``evaluator_jax.grid_fn`` = jit(vmap(vmap))); the
    numpy backend loops per point and is the parity reference;
  * :func:`solve_grid` — *batched solver searches*: ``method="ga"``
    (DESIGN.md §10) evolves same-shape points as islands of one
    device-resident ``jit(vmap(scan))`` call (:mod:`repro.core.ga_jax`);
    ``method="miqp"`` (DESIGN.md §12) runs the lattice-enumeration MIQP
    engine (:mod:`repro.core.miqp_jax`) with same-shape points batched
    along the grid axis of its chunked scoring calls. The numpy backend
    runs the host engines per point and is the fallback/reference;
  * a process-wide result cache keyed by content fingerprints
    (backend + task ops + HWConfig + options + partition bytes for
    evaluation records; + objective and the full solver config —
    GAConfig or MIQPConfig, method-tagged — for solver records;
    segment-duration bytes + batch + the resolved PipelineConfig for
    pipelining records), so
    repeated baselines across figure scripts — e.g.
    ``run.py`` invoking fig8 then fig9 on the same workloads — are
    evaluated once per backend (backends agree only to rtol 1e-9, so
    records are not shared across them — results must not depend on
    evaluation order).

Every family runs through one loop, :func:`_sweep`: checkpointing, the
cache lookups, grouping by a shape key, one grouped device call per
group (or a host call per point) and the cache store. A family supplies
a :class:`_Family` record: its fingerprint, record copy, per-point host
path, shape key and grouped call.

Two orthogonal execution knobs ride on every sweep (DESIGN.md §15):

  * ``devices`` — ``"single" | "sharded" | "auto"`` shards each batched
    group's grid axis across the local devices via ``shard_map``
    (:mod:`repro.core.sweep_shard`). Sharding is *result-neutral*: solo
    == batched == sharded bit-for-bit, so the knob is normalized out of
    every cache fingerprint (:func:`_strip_devices`) and records are
    device-count-independent — one cache serves all modes.
  * ``checkpoint`` — a store path (or :class:`SweepCheckpointer`) makes
    the sweep persist its new cache records every ``checkpoint_every``
    points through :class:`repro.serve.cache_store.CacheStore`. Kill the
    process anywhere and a rerun pointed at the same store resumes:
    completed points load back as cache hits, only the tail recomputes.

Typical use (LS baselines for one figure)::

    points = [EvalPoint(task, hw) for hw in hws for task in tasks]
    recs = eval_sweep(points)                  # one compiled call
    recs[0]["latency"], recs[0]["edp"]
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import sys
import time
from typing import Any, Callable, Iterable, Sequence

import numpy as np

from ..runtime.spans import span
from .evaluator import EvalOptions, Evaluator
from .hw import HWConfig
from .workload import Partition, Task, group_counts, uniform_partition

__all__ = [
    "EvalPoint",
    "PipelinePoint",
    "cosearch_sweep",
    "eval_sweep",
    "grid",
    "run_grid",
    "solve_grid",
    "netsim_sweep",
    "pipeline_sweep",
    "clear_cache",
    "cache_stats",
    "export_cache",
    "import_cache",
    "SweepCheckpointer",
]


# --------------------------------------------------------------- generic grid
def grid(**axes: Iterable) -> list[dict[str, Any]]:
    """Named-axis cartesian product: ``grid(a=[1,2], b="xy")`` →
    ``[{"a":1,"b":"x"}, {"a":1,"b":"y"}, ...]``. Axis order follows the
    keyword order, last axis fastest (matches nested-loop reading)."""
    names = list(axes)
    values = [list(axes[n]) for n in names]
    return [dict(zip(names, combo)) for combo in itertools.product(*values)]


def run_grid(
    points: Sequence[dict[str, Any]],
    fn: Callable[..., Any],
    emit: Callable[[dict, Any, float], None] | None = None,
    progress: bool | str = False,
    checkpoint=None,
    checkpoint_every: int = 1,
) -> list[tuple[dict, Any, float]]:
    """Timed per-point driver for sweeps whose body stays per-point —
    external-solver work such as the HiGHS ``engine="milp"`` MIQP path
    or the pipelining MILP refinement (batched MIQP lattice solves go
    through :func:`solve_grid` with ``method="miqp"`` and pipelining
    grids through :func:`pipeline_sweep` instead, DESIGN.md §12/§13).
    Calls ``fn(**point)`` for every point, returning
    ``(point, result, microseconds)`` triples; ``emit`` (if given) is
    invoked per point for CSV-style reporting.

    ``progress`` writes a ``point i/N`` liveness line to **stderr**
    after each point — per-point solve time, aggregate points/sec, and
    an ETA for the remainder (pass a string to label the sweep) — so
    long solver grids show progress without a custom ``emit`` and
    without polluting piped-stdout CSV output.

    ``checkpoint`` (a store path or :class:`SweepCheckpointer`) flushes
    the process-wide result cache to disk every ``checkpoint_every``
    points: when ``fn`` runs cached sweeps internally (the usual case —
    per-point ``solve_grid``/``run_miqp`` wrappers), a killed grid
    resumes from the same store with completed points as cache hits."""
    label = progress if isinstance(progress, str) else "run_grid"
    ckpt = _resolve_checkpoint(checkpoint, checkpoint_every)
    out = []
    t_start = time.perf_counter()
    for i, pt in enumerate(points):
        t0 = time.perf_counter()
        res = fn(**pt)
        us = (time.perf_counter() - t0) * 1e6
        out.append((pt, res, us))
        if ckpt is not None and (i + 1) % ckpt.every == 0:
            ckpt.flush()
        if progress:
            done = i + 1
            elapsed = time.perf_counter() - t_start
            rate = done / elapsed if elapsed > 0 else float("inf")
            eta = (len(points) - done) / rate if rate > 0 else 0.0
            print(f"[sweep] {label} point {done}/{len(points)} "
                  f"{us:.0f}us ({rate:.1f} pts/s, eta {eta:.1f}s)",
                  file=sys.stderr)
        if emit is not None:
            emit(pt, res, us)
    if ckpt is not None:
        ckpt.flush()
    return out


# ----------------------------------------------------------- batched eval
@dataclasses.dataclass
class EvalPoint:
    """One grid point of the batched evaluator sweep.

    ``partition=None`` means the LS-uniform partition (the baseline of
    every figure); ``redist_mask=None`` follows ``Evaluator.evaluate``:
    redistribute on every chained pair iff ``options.redistribution``.
    """

    task: Task
    hw: HWConfig
    options: EvalOptions = EvalOptions()
    partition: Partition | None = None
    redist_mask: np.ndarray | None = None

    def resolved_partition(self) -> Partition:
        if self.partition is not None:
            return self.partition
        return uniform_partition(self.task, self.hw.X, self.hw.Y)


def _task_fingerprint(task: Task) -> tuple:
    return (task.name, tuple(task.ops))


def _strip_devices(obj):
    """Normalize the §15 ``devices`` execution knob out of a fingerprint
    component. Sharding is result-neutral — solo == batched == sharded,
    bit-for-bit — so records produced under any device mode (or device
    count) must share ONE cache entry; a fingerprint that embedded the
    knob would make a sharded run miss a single-device store."""
    if dataclasses.is_dataclass(obj) and hasattr(obj, "devices"):
        return dataclasses.replace(obj, devices="auto")
    return obj


def _flow_width(pt) -> int:
    """Link width of the point's flow sites in the jitted evaluator
    (``Topology.flow_sites``), 0 outside flow mode: part of every
    shape-group signature whose group stacks constants, so no group
    stacks a compacted network with a full one."""
    if pt.options.congestion != "flow":
        return 0
    return pt.hw.topology.flow_sites()[0].shape[1]


def _point_fingerprint(pt: EvalPoint, backend: str) -> tuple:
    part = pt.resolved_partition()
    rd = (None if pt.redist_mask is None
          else np.asarray(pt.redist_mask, dtype=bool).tobytes())
    # backend is part of the key: the two engines agree only to rtol
    # 1e-9 (not bitwise), so sharing records across backends would make
    # results depend on which backend touched a fingerprint first.
    return (
        backend,
        _task_fingerprint(pt.task),
        pt.hw,
        _strip_devices(pt.options),
        part.Px.tobytes(), part.Py.tobytes(), part.collectors.tobytes(),
        rd,
    )


_CACHE: dict[tuple, dict[str, Any]] = {}
_STATS = {"hits": 0, "misses": 0}
#: Numbers the ``sweep.eval`` / ``sweep.solve`` spans (``call``), so the
#: spans nested in one call can be told from the next call's.
_CALLS = itertools.count(1)


def _copy_record(rec: dict[str, Any]) -> dict[str, Any]:
    """Records cross the cache boundary by value — callers mutating a
    returned record (or its arrays) must not poison the process cache."""
    return {k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in rec.items()}


def clear_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0


def cache_stats() -> dict[str, int]:
    return dict(_STATS)


def _copy_cache_value(value):
    """Copy-by-value for any record family the cache holds: evaluation /
    netsim records are plain dicts (numpy arrays copied), solver records
    are ``GAResult``/``MIQPResult``/``PipelineResult`` dataclasses."""
    if isinstance(value, dict):
        return _copy_record(value)
    return _copy_solver_record(value)


def export_cache() -> dict[tuple, Any]:
    """Snapshot the process-wide result cache as ``{fingerprint: record}``
    (records copied by value — mutating the snapshot cannot poison the
    cache). The fingerprints are the exact §9/§10/§12/§13 cache keys, so
    a snapshot can be persisted and re-imported in another process
    (:mod:`repro.serve.cache_store`) without weakening the
    solo==batched contract: a key either matches exactly or misses."""
    return {k: _copy_cache_value(v) for k, v in _CACHE.items()}


def import_cache(entries: dict, replace: bool = False) -> int:
    """Merge ``{fingerprint: record}`` entries (an :func:`export_cache`
    snapshot, possibly from another process via the on-disk store) into
    the process-wide cache; returns the number of entries inserted.
    Existing keys win unless ``replace=True`` — records are exact, so a
    collision is by construction the same result and keeping the
    resident copy is the cheaper choice."""
    n = 0
    for k, v in entries.items():
        if replace or k not in _CACHE:
            _CACHE[k] = _copy_cache_value(v)
            n += 1
    return n


# ------------------------------------------------- checkpointed resume
class SweepCheckpointer:
    """Periodic persistence of the §9 result cache to an on-disk
    :class:`repro.serve.cache_store.CacheStore` (DESIGN.md §15).

    Construction *loads* the store into the process cache — a sweep
    pointed at the store of a killed run resumes with every completed
    point a cache hit — and remembers which fingerprints the store
    already holds. :meth:`flush` appends only the delta (cache entries
    not yet persisted); the store's append path tears at most the tail
    record on a crash, and :meth:`~repro.serve.cache_store.CacheStore.
    load` drops a torn tail, so a kill at ANY instant costs at most one
    unflushed chunk of points.

    ``every`` is the flush cadence in points (sweep functions chunk the
    grid by it); ``resumed`` counts the records imported at construction.
    """

    def __init__(self, path, every: int = 8):
        from ..serve.cache_store import CacheStore

        self.store = path if isinstance(path, CacheStore) else \
            CacheStore(path)
        self.every = max(1, int(every))
        entries = self.store.load()
        self.resumed = import_cache(entries)
        self._persisted = set(entries)
        self.flushes = 0

    def pending(self) -> int:
        """Cache entries not yet persisted to the store."""
        return sum(1 for k in _CACHE if k not in self._persisted)

    def flush(self) -> int:
        """Append every unpersisted cache entry; returns the count."""
        new = {k: v for k, v in _CACHE.items()
               if k not in self._persisted}
        if new:
            self.store.append(new)
            self._persisted.update(new)
            self.flushes += 1
        return len(new)


def _resolve_checkpoint(checkpoint, every: int):
    if checkpoint is None or isinstance(checkpoint, SweepCheckpointer):
        return checkpoint
    return SweepCheckpointer(checkpoint, every=every)


def _checkpointed(points, ckpt: SweepCheckpointer, straggler, run_chunk):
    """Drive a batched sweep in checkpoint-sized chunks: each chunk's
    records land in the process cache (the sweep bodies insert them) and
    :meth:`SweepCheckpointer.flush` persists the delta, so a kill loses
    at most the in-flight chunk. ``straggler`` (a
    :class:`repro.runtime.fault_tolerance.StragglerMonitor`) observes
    per-chunk wall time and flags outlier chunks to stderr — the §15
    liveness signal for heterogeneous shards."""
    out = []
    for c, s in enumerate(range(0, len(points), ckpt.every)):
        chunk = points[s:s + ckpt.every]
        t0 = time.perf_counter()
        out.extend(run_chunk(chunk))
        dt = time.perf_counter() - t0
        ckpt.flush()
        if straggler is not None and straggler.observe(c, dt):
            print(f"[sweep] straggler: chunk {c} "
                  f"(points {s}:{s + len(chunk)}) took {dt:.3f}s",
                  file=sys.stderr)
    return out


# ------------------------------------------------------- the sweep loop
@dataclasses.dataclass(frozen=True)
class _Family:
    """One sweep kind as :func:`_sweep` drives it (DESIGN.md §9).

    ``fingerprint(pt)`` is a point's cache key and ``copy`` moves its
    record across the cache boundary; ``solo(pt)`` computes one point on
    the host, ``batch(points)`` one shape group (all with the same
    ``key(pt)``) in one grouped device call, returning records in order.
    ``span`` names the call span (``sweep.eval`` / ``sweep.solve``);
    ``None`` opens none."""

    fingerprint: Callable[[Any], tuple]
    copy: Callable[[Any], Any]
    solo: Callable[[Any], Any] | None
    key: Callable[[Any], tuple] | None
    batch: Callable[[list], list] | None
    span: str | None = None


def _shape_key(pt: EvalPoint) -> tuple:
    """The shape-group signature of the families that stack evaluator
    constants (eval, GA, MIQP, co-search): the shapes of the stacked
    arrays, and the static :class:`EvalOptions` of the compiled call
    with the result-neutral ``devices`` knob stripped."""
    return (len(pt.task), pt.task.group_shape, pt.hw.X, pt.hw.Y,
            pt.hw.topology.n_entrances, _flow_width(pt),
            _strip_devices(pt.options))


def _call_devices(devices: str | None, default: str) -> str:
    """The §15 device mode of a sweep's grouped calls: the sweep-level
    ``devices`` wins; ``None`` defers to the family's ``default`` (the
    group's ``options.devices`` for evaluation, ``cfg.devices`` for the
    solvers and pipelining, ``"auto"`` for the netsim)."""
    return default if devices is None else devices


def _check_backend(backend: str, accepted=("numpy", "jax", "auto")):
    """Refuse a backend that is neither engine once ``"auto"`` is
    resolved; ``accepted`` is what the caller's argument may be."""
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown backend {backend!r}; one of {accepted}")


def _group_args(points) -> tuple:
    """A solver group's engine arguments: its tasks, its packages and
    the options the group shares."""
    return ([pt.task for pt in points], [pt.hw for pt in points],
            points[0].options)


def _sweep(points, fam: _Family, batched: bool, cache: bool, checkpoint,
           checkpoint_every: int, straggler) -> list:
    """The loop every sweep family shares: checkpointed chunks, the call
    span, cache lookups, then per shape group one ``fam.batch`` call
    (``batched``) or ``fam.solo`` per point, and the cache inserts.
    Returns records aligned with ``points``."""
    ckpt = _resolve_checkpoint(checkpoint, checkpoint_every)
    if ckpt is not None:
        if not cache:
            raise ValueError("checkpointing requires cache=True — "
                             "records persist through the result cache")
        return _checkpointed(
            points, ckpt, straggler,
            lambda c: _sweep(c, fam, batched, True, None, 1, None))
    with (span(fam.span, call=next(_CALLS), points=len(points))
          if fam.span else contextlib.nullcontext()):
        records, todo, fps = _lookup(points, cache, fam.fingerprint,
                                     fam.copy)
        if todo and not batched:
            for i in todo:
                records[i] = fam.solo(points[i])
        elif todo:
            groups: dict[tuple, list[int]] = {}
            with span("sweep.group") as sp:
                for i in todo:
                    groups.setdefault(fam.key(points[i]), []).append(i)
                sp.set_metadata(groups=len(groups))
            for idxs in groups.values():
                outs = fam.batch([points[i] for i in idxs])
                for i, out in zip(idxs, outs):
                    records[i] = out
        if cache:
            # records enter the cache by value, like every hit leaves it
            with span("sweep.records", records=len(todo)):
                for i in todo:
                    _CACHE[fps[i]] = fam.copy(records[i])
    return records


def _lookup(points, cache: bool, fingerprint, copy):
    """The cache lookups of one sweep call: ``(records, todo, fps)``,
    records filled for the hits, ``todo`` the indices left to compute;
    ``fingerprint(pt)`` is a point's cache key. Spanned as
    ``sweep.lookup`` with the call's hits and misses."""
    records: list = [None] * len(points)
    todo: list[int] = []
    fps: list[tuple | None] = [None] * len(points)
    with span("sweep.lookup") as sp:
        for i in range(len(points)):
            if cache:
                fp = fingerprint(points[i])
                fps[i] = fp
                hit = _CACHE.get(fp)
                if hit is not None:
                    _STATS["hits"] += 1
                    records[i] = copy(hit)
                    continue
                _STATS["misses"] += 1
            todo.append(i)
        misses = len(todo) if cache else 0
        sp.set_metadata(hits=len(points) - len(todo), misses=misses)
    return records, todo, fps


def _record(point: EvalPoint, out: dict[str, np.ndarray], i: int | tuple
            ) -> dict[str, Any]:
    """Extract one point's scalars/arrays from a batched output dict."""
    def at(v):
        return v[i]

    rec = {
        "task": point.task.name,
        "hw": point.hw,
        "options": point.options,
        "latency": float(at(out["latency"])),
        "energy": float(at(out["energy"])),
        "edp": float(at(out["edp"])),
        "t_in": np.asarray(at(out["t_in"])),
        "t_comp": np.asarray(at(out["t_comp"])),
        "t_out": np.asarray(at(out["t_out"])),
    }
    for k in ("E_sram", "E_mac", "E_mem", "E_nop"):
        rec[k] = float(at(out[k]))
    return rec


def _genome(pt: EvalPoint, ev: Evaluator):
    part = pt.resolved_partition()
    if pt.redist_mask is None:
        rd = ev.chain_valid & pt.options.redistribution
    else:
        rd = np.asarray(pt.redist_mask, dtype=bool) & ev.chain_valid
        if not pt.options.redistribution:
            rd = np.zeros_like(rd)
    return (part.Px.astype(np.float64), part.Py.astype(np.float64),
            part.collectors.astype(np.float64), rd.astype(np.float64))


def eval_sweep(
    points: Sequence[EvalPoint],
    backend: str = "jax",
    cache: bool = True,
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list[dict[str, Any]]:
    """Evaluate every point; returns records aligned with ``points``.

    JAX backend: uncached points are grouped by shape signature + static
    options and each group is evaluated in one compiled call (consts and
    genomes stacked on a leading grid axis). Numpy backend: per-point
    reference loop — same records, used by the parity tests.

    ``devices`` (DESIGN.md §15) shards each group's grid axis across
    local devices — result-neutral, see the module docstring; ``None``
    defers to each point's ``options.devices``. ``checkpoint`` (a store
    path or :class:`SweepCheckpointer`) persists records every
    ``checkpoint_every`` points for kill/resume; requires ``cache=True``.
    """
    _check_backend(backend, ("numpy", "jax"))
    fam = _Family(lambda pt: _point_fingerprint(pt, backend), _copy_record,
                  _eval_solo, _shape_key,
                  lambda group: _eval_batch(group, devices), span="sweep.eval")
    return _sweep(points, fam, backend == "jax", cache, checkpoint,
                  checkpoint_every, straggler)


def _eval_solo(pt: EvalPoint) -> dict[str, Any]:
    """One point through the numpy reference evaluator."""
    ev = Evaluator(pt.task, pt.hw, pt.options, backend="numpy")
    Px, Py, co, rd = _genome(pt, ev)
    out = ev.evaluate_batch(Px[None], Py[None], co[None], rd[None])
    return _record(pt, out, 0)


def _eval_batch(points, devices) -> list[dict[str, Any]]:
    """One shape group: constants and genomes stacked on a leading grid
    axis, evaluated by one compiled call."""
    from . import evaluator_jax

    with span("sweep.consts", points=len(points)) as sp:
        evs = [Evaluator(pt.task, pt.hw, pt.options, backend="jax")
               for pt in points]
        consts = [ev.consts() for ev in evs]
        stacked = {k: np.stack([c[k] for c in consts]) for k in consts[0]}
        genomes = [_genome(pt, ev) for pt, ev in zip(points, evs)]
        # [G,1,n,X], [G,1,n,Y], [G,1,n], [G,1,n]
        Px, Py, co, rd = (np.stack([g[j] for g in genomes])[:, None]
                          for j in range(4))
        sp.set_metadata(bytes=sum(
            a.nbytes for a in (*stacked.values(), Px, Py, co, rd)),
            **group_counts([pt.task for pt in points]))
    out = evaluator_jax.grid_evaluate(
        stacked, points[0].options, Px, Py, co, rd,
        devices=_call_devices(devices, points[0].options.devices))
    with span("sweep.records", records=len(points)):
        return [_record(pt, out, (g, 0)) for g, pt in enumerate(points)]


# ------------------------------------------------------ batched netsim
def _rate_fp(v):
    """Fingerprint component for a scalar-or-array rate: heterogeneous
    per-chiplet capacities key by content bytes, scalars stay plain
    floats (so every pre-hetero cache key is unchanged)."""
    a = np.asarray(v, dtype=np.float64)
    return float(a) if a.ndim == 0 else a.tobytes()


def _netsim_fingerprint(net, message_bytes: float, backend: str) -> tuple:
    ms = getattr(net, "mem_scale", None)
    return ("netsim", backend, net.X, net.Y, _rate_fp(net.bw_nop),
            float(net.bw_mem),
            None if ms is None else _rate_fp(ms),
            tuple(net.attach), float(message_bytes))


def netsim_sweep(
    nets: Sequence,
    message_bytes: float,
    backend: str = "jax",
    cache: bool = True,
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list[dict[str, Any]]:
    """Run the all-chiplets-pull flow simulation on every
    :class:`repro.core.netsim.MeshNet`; returns records aligned with
    ``nets`` (DESIGN.md §11).

    JAX backend: uncached nets are grouped by mesh shape (the
    :mod:`repro.core.topology` link space is a pure function of (X, Y) —
    capacities and attachment sets are data) and each group's whole
    (memory × placement × bandwidth) grid runs through ONE compiled
    ``lax.while_loop`` call (:func:`repro.core.netsim_jax.
    simulate_pull_batch`). Numpy backend: the per-net vectorized host
    engine — the parity reference. Records carry ``latency`` (seconds),
    per-flow ``done`` times and per-link ``link_bytes`` over the dense
    link space, and share the process-wide result cache (fingerprint:
    backend, mesh shape, bandwidths, attachment set, message size).

    ``devices`` / ``checkpoint`` / ``straggler`` follow the §15 contract
    (module docstring): sharding is result-neutral and checkpointing
    persists records for kill/resume."""
    from . import netsim

    _check_backend(backend, ("numpy", "jax"))

    def solo(net):
        out = netsim.simulate_flows(
            net.pull_incidence(), net.link_caps(),
            np.full(net.X * net.Y, float(message_bytes)))
        return {"latency": float(out["latency"]),
                "done": out["done"], "link_bytes": out["link_bytes"]}

    def batch(group):
        from . import netsim_jax

        out = netsim_jax.simulate_pull_batch(
            np.stack([net.link_caps() for net in group]),
            np.stack([net.pull_incidence() for net in group]),
            np.full((len(group), group[0].X * group[0].Y),
                    float(message_bytes)),
            devices=_call_devices(devices, "auto"))
        return [{"latency": float(out["latency"][g]), "done": out["done"][g],
                 "link_bytes": out["link_bytes"][g]}
                for g in range(len(group))]

    fam = _Family(
        lambda net: _netsim_fingerprint(net, message_bytes, backend),
        _copy_record, solo, lambda net: (net.X, net.Y), batch)
    return _sweep(nets, fam, backend == "jax", cache, checkpoint,
                  checkpoint_every, straggler)


# ----------------------------------------------------------- batched solves
def _solver_fingerprint(pt: EvalPoint, method: str, backend: str,
                        objective: str, cfg) -> tuple:
    """Cache key for a solver search. The method tag and the full
    (frozen, hashable) solver config — GAConfig or MIQPConfig — are part
    of the key, so GA and MIQP records on the same point never collide
    and any hyperparameter change is a different record; so is the
    backend: the GA engines draw from different RNGs and the lattice
    scorers agree only to rtol 1e-9 (arg-min ties could flip), so
    records must never be served across backends. The §15 ``devices``
    knob is normalized out of both the options and the config
    (:func:`_strip_devices`) — sharding never changes a result."""
    return (
        method, backend,
        _task_fingerprint(pt.task),
        pt.hw,
        _strip_devices(pt.options),
        objective,
        _strip_devices(cfg),
    )


def _copy_solver_record(rec):
    from .cosearch import CoSearchResult
    from .ga import GAResult
    from .miqp import MIQPResult
    from .multitenant import MultiTenantResult
    from .pipelining import PipelineResult

    if isinstance(rec, PipelineResult):
        return dataclasses.replace(rec)  # all fields immutable scalars
    if isinstance(rec, MultiTenantResult):
        return rec.copy()
    if isinstance(rec, CoSearchResult):
        return CoSearchResult(
            partition=rec.partition.copy(),
            redist_mask=rec.redist_mask.copy(),
            diagonal=rec.diagonal,
            seg_mask=rec.seg_mask.copy(),
            objective=rec.objective,
            edp=rec.edp,
            latency=rec.latency,
            energy=rec.energy,
            front={k: v.copy() for k, v in rec.front.items()},
            history=rec.history.copy(),
            evaluations=rec.evaluations,
        )
    if isinstance(rec, MIQPResult):
        return MIQPResult(
            partition=rec.partition.copy(),
            redist_mask=rec.redist_mask.copy(),
            objective=rec.objective,
            milp_status=rec.milp_status,
            milp_objective=rec.milp_objective,
            engine=rec.engine,
        )
    return GAResult(
        partition=rec.partition.copy(),
        redist_mask=rec.redist_mask.copy(),
        objective=rec.objective,
        history=rec.history.copy(),
        evaluations=rec.evaluations,
    )


def solve_grid(
    points: Sequence[EvalPoint],
    objective: str = "latency",
    cfg=None,
    backend: str = "jax",
    cache: bool = True,
    method: str = "ga",
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list:
    """Run one solver search per point; returns records aligned with
    ``points`` — ``GAResult`` for ``method="ga"`` (DESIGN.md §10),
    ``MIQPResult`` for ``method="miqp"`` (DESIGN.md §12).

    JAX backend: uncached points are grouped by shape signature
    (:func:`_shape_key`, static options included) and each group
    batches through ONE compiled program per call: GA searches evolve
    as *islands* of one ``jit(vmap(scan))`` call
    (:func:`repro.core.ga_jax.solve_islands`); MIQP lattice searches
    share the grid axis of the chunked scoring calls
    (:func:`repro.core.miqp_jax.solve_lattice_batch`). Numpy
    backend: per-point host engines — the fallback used by ``run.py
    --backend numpy``. A point's result (and its cache record) is
    identical whether it is solved alone or batched with others: GA
    island RNG depends only on ``cfg.seed``, and the lattice budgets are
    deterministic candidate counts.

    ``pt.partition`` / ``pt.redist_mask`` are ignored — a solve searches
    the genome space, it does not score a fixed schedule.
    ``backend="auto"`` resolves before fingerprinting (by
    ``cfg.population`` for GA, ``cfg.score_chunk`` for MIQP — the
    DESIGN.md §8 threshold), so auto-resolved records share the cache
    with their concrete-backend equivalents; likewise
    ``MIQPConfig(engine="auto")`` resolves first. ``method="miqp"`` with
    ``engine="milp"`` cannot batch — those points run serially through
    :func:`repro.core.miqp.run_miqp` (still cached).

    ``devices`` (DESIGN.md §15) shards each group's island/grid axis
    across local devices — result-neutral and fingerprint-invisible;
    ``None`` defers to ``cfg.devices``. ``checkpoint`` (a store path or
    :class:`SweepCheckpointer`) persists solver records every
    ``checkpoint_every`` points for kill/resume (``cache=True`` only);
    ``straggler`` flags outlier chunk wall-times to stderr."""
    ckpt_args = (checkpoint, checkpoint_every, straggler)
    if method == "miqp":
        return _solve_grid_miqp(points, objective, cfg, backend, cache,
                                devices, *ckpt_args)
    if method in ("cosearch", "multitenant"):
        family = cosearch_sweep if method == "cosearch" else \
            multitenant_sweep
        return family(points, objective, cfg, backend, cache, devices,
                      *ckpt_args)
    if method != "ga":
        raise ValueError(f"unknown method {method!r}; "
                         f"one of ('ga', 'miqp', 'cosearch', "
                         f"'multitenant')")
    from .evaluator import resolve_auto_backend
    from .ga import GAConfig, run_ga

    if cfg is None:
        cfg = GAConfig()
    backend = resolve_auto_backend(backend, cfg.population)
    _check_backend(backend)

    def batch(group):
        from . import ga_jax

        return ga_jax.solve_islands(
            *_group_args(group), objective, cfg,
            devices=_call_devices(devices, cfg.devices))

    fam = _Family(
        lambda pt: _solver_fingerprint(pt, "ga", backend, objective, cfg),
        _copy_solver_record,
        lambda pt: run_ga(pt.task, pt.hw, objective, pt.options, cfg,
                          backend="numpy", engine="vectorized"),
        _shape_key, batch, span="sweep.solve")
    return _sweep(points, fam, backend == "jax", cache, *ckpt_args)


# ------------------------------------------------- batched co-search
def cosearch_sweep(
    points: Sequence[EvalPoint],
    objective: str = "edp",
    cfg=None,
    backend: str = "jax",
    cache: bool = True,
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list:
    """Run one fused joint search (partition × diagonal links × pipeline
    segmentation, DESIGN.md §16) per point; returns
    :class:`repro.core.cosearch.CoSearchResult` records aligned with
    ``points`` — also reachable as ``solve_grid(method="cosearch")``.

    Uncached points are grouped by shape signature (:func:`_shape_key`,
    static options included) and each group evolves as islands of ONE
    ``jit(vmap(scan))`` call
    (:func:`repro.core.cosearch.cosearch_islands`). A point's record is
    identical solo or batched (island RNG depends only on ``cfg.seed``,
    budgets are deterministic counts), so the §9 cache contract holds:
    records are method-tagged ``"cosearch"`` and keyed by the full
    frozen :class:`CoSearchConfig`.

    The diag gene *searches* the link axis, so ``pt.hw.diagonal_links``
    is normalized to ``False`` before fingerprinting and solving — plain
    and diagonal variants of the same mesh share one record.
    ``pt.partition`` / ``pt.redist_mask`` are ignored, like
    :func:`solve_grid`. Only the JAX backend exists (the fitness chains
    traced engines end-to-end); ``backend="auto"`` resolves to it.

    ``devices`` (DESIGN.md §15) shards each group's island axis —
    result-neutral and fingerprint-invisible; ``None`` defers to
    ``cfg.devices``. ``checkpoint`` / ``checkpoint_every`` /
    ``straggler`` behave exactly like :func:`solve_grid`."""
    from .cosearch import CoSearchConfig, cosearch_islands

    if cfg is None:
        cfg = CoSearchConfig()
    if not isinstance(cfg, CoSearchConfig):
        raise TypeError(f"cosearch_sweep needs a CoSearchConfig, "
                        f"got {type(cfg).__name__}")
    if backend == "auto":
        backend = "jax"
    if backend != "jax":
        raise ValueError(f"unknown backend {backend!r} for cosearch; "
                         f"the fused fitness only exists on 'jax' "
                         f"('auto' resolves to it)")
    points = [dataclasses.replace(
        pt, hw=dataclasses.replace(pt.hw, diagonal_links=False))
        for pt in points]
    fam = _Family(
        lambda pt: _solver_fingerprint(pt, "cosearch", "jax", objective,
                                       cfg),
        _copy_solver_record, None, _shape_key,
        lambda group: cosearch_islands(
            *_group_args(group), objective, cfg,
            devices=_call_devices(devices, cfg.devices)))
    return _sweep(points, fam, True, cache, checkpoint, checkpoint_every,
                  straggler)


# ---------------------------------------------- multi-tenant placement
@dataclasses.dataclass
class MultiTenantPoint:
    """One grid point of the multi-tenant placement sweep (DESIGN.md
    §18): several co-resident tasks on ONE (possibly heterogeneous)
    package, searched by ``solve_grid(method="multitenant")``."""

    tasks: tuple
    hw: HWConfig
    options: EvalOptions = EvalOptions()


def _multitenant_fingerprint(pt: MultiTenantPoint, backend: str,
                             objective: str, cfg) -> tuple:
    """Cache key for a multi-tenant search: tenant task tuple (order
    matters — bands are assigned in tenant order), the full hetero
    HWConfig (chiplet classes/assignment are hashable fields), and the
    frozen config with the §15 devices knob stripped at both levels
    (the outer config and the nested inner-solver config)."""
    inner = _strip_devices(cfg.cfg)
    return (
        "multitenant", backend,
        tuple(_task_fingerprint(t) for t in pt.tasks),
        pt.hw,
        _strip_devices(pt.options),
        objective,
        _strip_devices(dataclasses.replace(cfg, cfg=inner)),
    )


def multitenant_sweep(
    points: Sequence[MultiTenantPoint],
    objective: str = "edp",
    cfg=None,
    backend: str = "jax",
    cache: bool = True,
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list:
    """Run one multi-tenant placement search per point; returns
    :class:`repro.core.multitenant.MultiTenantResult` records aligned
    with ``points`` — also reachable as
    ``solve_grid(method="multitenant")`` (DESIGN.md §18).

    The outer assignment loop is a host loop (band compositions are
    few); the inner per-tenant solves and exact re-scores go through
    :func:`solve_grid` / :func:`eval_sweep`, so they batch per region
    shape and share the process cache — identical region solves across
    assignments (and across points) dedupe to one engine call. All
    budgets are deterministic counts, so records obey the §9 solo ==
    batched == served contract.

    ``checkpoint`` / ``straggler`` follow the §15 contract; ``devices``
    threads through to the inner engines and is fingerprint-invisible."""
    from .multitenant import MultiTenantConfig, solve_multitenant

    if cfg is None:
        cfg = MultiTenantConfig()
    if not isinstance(cfg, MultiTenantConfig):
        raise TypeError(f"multitenant_sweep needs a MultiTenantConfig, "
                        f"got {type(cfg).__name__}")
    if backend == "auto":
        backend = "jax"
    _check_backend(backend)
    fam = _Family(
        lambda pt: _multitenant_fingerprint(pt, backend, objective, cfg),
        _copy_solver_record,
        lambda pt: solve_multitenant(
            pt.tasks, pt.hw, objective, pt.options, cfg,
            backend=backend, cache=cache, devices=devices),
        None, None)
    return _sweep(points, fam, False, cache, checkpoint, checkpoint_every,
                  straggler)


# ------------------------------------------------- batched pipelining
@dataclasses.dataclass
class PipelinePoint:
    """One grid point of the batched RCPSP pipelining sweep
    (DESIGN.md §13): per-op ``(name, t_in, t_comp, t_out)`` segment
    durations for ONE sample (``EvalResult.segments()`` /
    ``ScheduleResult.segments()``) plus the batch size to pipeline."""

    segments: Sequence[tuple[str, float, float, float]]
    batch: int

    def durations(self) -> np.ndarray:
        """``[n_ops, 3]`` float64 durations, clamped like ``build_jobs``
        (one conversion shared with the engines, so the clamping
        contract — and the cache fingerprint built on it — cannot
        drift)."""
        from .pipelining import _segment_durations

        return _segment_durations(self.segments).reshape(-1, 3)


def _pipeline_fingerprint(pt: PipelinePoint, cfg) -> tuple:
    """Cache key for a pipelining record: method tag, the resolved
    (frozen) :class:`~repro.core.pipelining.PipelineConfig` — engine and
    backend included — segment-duration bytes and batch. The engines are
    bit-identical (DESIGN.md §13), but the backend stays in the key for
    consistency with every other record family."""
    return ("pipeline", _strip_devices(cfg), pt.durations().tobytes(),
            int(pt.batch))


def pipeline_sweep(
    points: Sequence[PipelinePoint],
    cfg=None,
    backend: str = "jax",
    cache: bool = True,
    devices: str | None = None,
    checkpoint=None,
    checkpoint_every: int = 8,
    straggler=None,
) -> list:
    """Schedule every pipelining point; returns
    :class:`~repro.core.pipelining.PipelineResult` records aligned with
    ``points`` (DESIGN.md §13).

    JAX backend: uncached points are grouped by (n_ops, batch) — the
    chain structure is a pure function of that pair; durations are data —
    and each group schedules through ONE compiled
    ``pipelining_jax.schedule_batch`` call. A point's record is identical
    whether it is scheduled alone or batched (bit-identical, the §9 cache
    invariant). ``backend="numpy"`` runs the host frontier loop per
    point (the parity reference); ``engine="python"``/``"milp"`` configs
    run the serial engines per point — milp cannot batch — with records
    still cached. A non-``"auto"`` ``cfg.backend`` wins over the
    sweep-level ``backend`` argument (the :class:`PipelineConfig`
    contract); ``"auto"`` resolves to jax — grid batching always wins
    here, and the engines agree bit-for-bit, so the resolution is purely
    a performance choice.

    ``devices`` / ``checkpoint`` / ``straggler`` follow the §15 contract
    (module docstring); ``devices=None`` defers to ``cfg.devices``."""
    from .pipelining import (PipelineConfig, PipelineResult,
                             pipeline_batch, resolve_auto_pipeline_engine,
                             sequential_makespan)

    if cfg is None:
        cfg = PipelineConfig()
    engine = resolve_auto_pipeline_engine(cfg.engine)
    backend = cfg.backend if cfg.backend != "auto" else backend
    backend = "jax" if backend == "auto" else backend
    _check_backend(backend)
    if engine != "vectorized":
        backend = "numpy"        # serial engines run on host
    # Fingerprint the *resolved* config so auto-selected records share
    # the cache with their concrete equivalents (the §12 rule).
    cfg = dataclasses.replace(cfg, engine=engine, backend=backend,
                              devices=_call_devices(devices, cfg.devices))

    def batch(group):
        from . import pipelining_jax

        B = int(group[0].batch)
        out = pipelining_jax.schedule_batch(
            np.stack([pt.durations() for pt in group]), B,
            devices=cfg.devices)
        return [PipelineResult(B, sequential_makespan(pt.segments, B),
                               float(out["makespan"][g]),
                               engine="vectorized")
                for g, pt in enumerate(group)]

    fam = _Family(
        lambda pt: _pipeline_fingerprint(pt, cfg), _copy_solver_record,
        lambda pt: pipeline_batch(pt.segments, pt.batch, config=cfg),
        lambda pt: (len(pt.segments), int(pt.batch)), batch)
    return _sweep(points, fam, backend == "jax", cache, checkpoint,
                  checkpoint_every, straggler)


def _solve_grid_miqp(points, objective, cfg, backend, cache, devices,
                     checkpoint, checkpoint_every, straggler) -> list:
    """``solve_grid`` body for ``method="miqp"`` (DESIGN.md §12)."""
    from .evaluator import resolve_auto_backend
    from .miqp import MIQPConfig, resolve_auto_engine, run_miqp

    if cfg is None:
        cfg = MIQPConfig()
    engine = resolve_auto_engine(cfg.engine)
    backend = (resolve_auto_backend(backend, cfg.score_chunk)
               if engine == "lattice" else "numpy")
    _check_backend(backend)
    # Fingerprint the *resolved* config so auto-selected records share
    # the cache with their concrete equivalents.
    cfg = dataclasses.replace(cfg, engine=engine, backend=backend,
                              devices=_call_devices(devices, cfg.devices))

    def batch(group):
        from . import miqp_jax

        return miqp_jax.solve_lattice_batch(*_group_args(group), objective,
                                            cfg)

    # milp cannot batch; the numpy lattice is the per-point reference.
    fam = _Family(
        lambda pt: _solver_fingerprint(pt, "miqp", backend, objective, cfg),
        _copy_solver_record,
        lambda pt: run_miqp(pt.task, pt.hw, objective, pt.options, cfg,
                            engine=engine),
        _shape_key, batch)
    return _sweep(points, fam, backend == "jax", cache, checkpoint,
                  checkpoint_every, straggler)
