"""Fused cross-layer co-search — one jitted genome over partition ×
diagonal links × pipeline segmentation, with a batched Pareto front
(DESIGN.md §16).

The paper optimizes partition (GA/MIQP), link configuration, and the
pipeline schedule as *separate passes*. Every pass is now a traced JAX
engine (DESIGN.md §8–§13), so the passes can fuse: this module evolves a
genome spanning all three layers and scores it end-to-end in ONE jitted
fitness that chains the analytical evaluator
(:func:`repro.core.evaluator_jax._eval_single` — regime or flow
congestion) into the vectorized RCPSP scheduler
(:func:`repro.core.pipelining_jax.sgs_instance`):

  * **Genome** — ``Px [n,X]`` / ``Py [n,Y]`` / ``collectors [n]`` /
    ``redist [n]`` (the GA genome, DESIGN.md §10) plus ``diag`` (a
    scalar link-budget gene selecting the plain or the diagonal-link
    mesh — both meshes' evaluator constants ship to device and the gene
    picks per candidate, so link ablation is *searched*, not a separate
    pass) and ``seg [n]`` (a boundary mask: ``seg[i]`` merges a pipeline
    stage boundary after op ``i``; segment durations are a one-hot
    merge of the evaluator's per-op ``(t_in, t_comp, t_out)`` phases,
    so segmentation is searched jointly with the partition that shapes
    those phases).
  * **Fused fitness** — evaluator → segment merge → traced chain
    priorities → SGS makespan at ``cfg.batch`` samples; returns the
    objective vector ``(EDP, latency, energy)`` with EDP/latency on the
    *pipelined* per-sample latency (``makespan / batch``).
  * **Pareto archive in the scan** — each generation merges the
    population's objective vectors into a fixed-size device archive
    (pairwise dominance + deterministic truncation, lowest-EDP
    non-dominated rows kept), so ONE compiled call returns the full
    EDP × latency × energy front instead of N single-objective solves.
  * **Gradient-guided seeding** — the integer partition lattice relaxes
    to a continuous simplex (``softmax(logits) * M``) and the diag gene
    to a sigmoid; ``jax.grad`` of the *smooth* fused fitness
    (``_eval_single(smooth=True)`` + the busiest-resource pipeline
    lower bound ``max(B·Σt_comm, B·Σt_comp, Σt)``) drives a fixed-count
    projected descent whose rounded proposals seed the population
    (rows 2..) and re-anchor the MIQP lattice enumeration
    (:func:`miqp_anchor` → ``miqp_jax._Space(anchor=...)``). All
    budgets are deterministic step counts — never wall-clock.

Exactness: island batching follows the §10 contract — per-island host
init seeded by ``cfg.seed`` alone, per-generation keys shared across
islands — so a point's :class:`CoSearchResult` is bitwise identical
solo, batched, or sharded (``devices=`` via
:mod:`repro.core.sweep_shard`), and
:func:`repro.core.sweep.cosearch_sweep` caches records under
method-tagged fingerprints (§9).

Host-side Pareto utilities (:func:`dominates`, :func:`pareto_mask`,
:class:`ParetoArchive`) mirror the device archive for result extraction
and property tests (``tests/test_pareto_archive.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, random

from .evaluator import EvalOptions, Evaluator
from .evaluator_jax import _eval_single
from .ga import MOVE_ATTEMPTS, _random_population_vec
from .ga_jax import _move_units
from .hw import HWConfig
from .pipelining_jax import chain_priorities_jnp, sgs_instance
from .workload import (Partition, Task, clamp_partition_to_domain,
                       uniform_partition)
from .x64 import cumsum_seq, x64

__all__ = [
    "OBJECTIVES",
    "CoSearchConfig",
    "CoSearchResult",
    "dominates",
    "pareto_mask",
    "ParetoArchive",
    "cosearch_islands",
    "run_cosearch",
    "miqp_anchor",
]

#: Objective vector layout of the fused fitness (all minimized): EDP and
#: latency are *pipelined* (makespan / batch); energy is schedule-free.
OBJECTIVES = ("edp", "latency", "energy")

#: Evaluator-constant keys that differ between the plain and the
#: diagonal-link mesh (same shapes — entrances, masks and the flow
#: network are topology-flag-independent); the diag gene selects or, in
#: the smooth relaxation, interpolates exactly these.
DIAG_KEYS = ("hA", "hW", "h_min", "links")

# Carry tuple layout (leaves gain a leading island axis under vmap):
# (Px, Py, co, rd, diag, seg,                      population genes
#  arch_obj, aPx, aPy, aco, ard, adiag, aseg,      Pareto archive
#  best_obj, best_vec, bPx, bPy, bco, brd, bdiag, bseg,
#  flat, steps)
_BEST_OBJ, _BEST_VEC, _FLAT, _STEPS = 13, 14, 21, 22


@dataclasses.dataclass(frozen=True)
class CoSearchConfig:
    """Hyperparameters of the joint search. Frozen + hashable — the full
    config is part of the §9 cache fingerprint and the serve-layer
    CallKey. Every budget is a deterministic count (generations,
    descent steps, archive slots), never wall-clock, so a record is
    reproducible by key alone."""

    population: int = 64
    generations: int = 64
    elite: int = 4
    tournament: int = 3
    p_crossover: float = 0.85
    p_mutate_partition: float = 0.5
    p_mutate_collector: float = 0.2
    p_mutate_redist: float = 0.15
    p_mutate_diag: float = 0.15
    p_mutate_seg: float = 0.25
    slack: int = 2
    patience: int = 64
    seed: int = 0
    #: samples pipelined by the fused fitness (the fig11/fig13 batch).
    batch: int = 4
    #: extra comm-in seconds charged per active pipeline segment — a
    #: sync/drain cost that makes coarse segmentation non-free (0.0
    #: keeps the paper's free-segmentation reading).
    seg_overhead: float = 0.0
    #: device Pareto-archive capacity (finite rows become the front).
    archive_size: int = 32
    #: share of the population replaced by projected-gradient proposals
    #: (rows 2..; rows 0/1 stay the uniform partition on each mesh).
    seed_fraction: float = 0.25
    seed_steps: int = 32
    seed_lr: float = 0.3
    seed_starts: int = 4
    freeze_redist: bool = False
    backend: str = "jax"
    devices: str = "auto"

    def __post_init__(self):
        if self.population < 2:
            raise ValueError("population must be >= 2")
        if self.archive_size < 1:
            raise ValueError("archive_size must be >= 1")
        if not 0.0 <= self.seed_fraction <= 1.0:
            raise ValueError("seed_fraction must be in [0, 1]")
        if self.batch < 1:
            raise ValueError("batch must be >= 1")
        if self.seg_overhead < 0.0:
            raise ValueError("seg_overhead must be >= 0")
        if self.seed_steps < 0 or self.seed_starts < 0:
            raise ValueError("seed_steps/seed_starts must be >= 0")


@dataclasses.dataclass
class CoSearchResult:
    """One point's joint-search result: the best genome on the scalar
    search objective plus the batched Pareto front.

    ``front`` maps ``"edp"/"latency"/"energy"`` to aligned ``[F]``
    arrays and carries the full genome per front row (``"Px" [F,n,X]``,
    ``"Py" [F,n,Y]``, ``"collectors"/"redist"/"seg" [F,n]``,
    ``"diag" [F]``), canonically sorted by (edp, latency, energy) and
    mutually non-dominated. The archive is bounded
    (``cfg.archive_size``), keeping lowest-EDP non-dominated rows — the
    *best* genome is tracked exactly and separately, like the GA's."""

    partition: Partition
    redist_mask: np.ndarray
    diagonal: bool
    seg_mask: np.ndarray
    objective: float
    edp: float
    latency: float
    energy: float
    front: dict[str, np.ndarray]
    history: np.ndarray
    evaluations: int


# ------------------------------------------------ host Pareto utilities
def dominates(a, b) -> bool:
    """Strict Pareto dominance (minimization): every component of ``a``
    <= the matching component of ``b`` and at least one strictly <."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    return bool(np.all(a <= b) and np.any(a < b))


def pareto_mask(points) -> np.ndarray:
    """``[N] bool`` — non-dominated rows of ``points [N, d]``, with exact
    duplicates keeping only their first occurrence (so the masked set is
    a minimal front: no member dominates or equals another)."""
    pts = np.asarray(points, dtype=np.float64)
    if pts.ndim != 2:
        pts = pts.reshape(len(pts), -1)
    N = len(pts)
    le = np.all(pts[:, None, :] <= pts[None, :, :], axis=-1)
    lt = np.any(pts[:, None, :] < pts[None, :, :], axis=-1)
    dominated = np.any(le & lt, axis=0)
    eq = np.all(pts[:, None, :] == pts[None, :, :], axis=-1)
    idx = np.arange(N)
    dup = np.any(eq & (idx[:, None] < idx[None, :]), axis=0)
    return ~(dominated | dup)


class ParetoArchive:
    """Host mirror of the device archive: insert points, read the front.

    The archive keeps every non-dominated point (pruning newly dominated
    members on insert); :meth:`front` returns the canonical
    (value-sorted) front, optionally truncated to ``k`` rows by the same
    lowest-first rule the device archive uses. Because membership is a
    pure function of the *set* of inserted points, the front is
    invariant to insertion order (``tests/test_pareto_archive.py``
    pins this with hypothesis permutations)."""

    def __init__(self):
        self._points: list[tuple[np.ndarray, object]] = []

    def __len__(self) -> int:
        return len(self._points)

    def insert(self, point, payload=None) -> bool:
        """Add ``point`` (any 1-D objective vector); returns True if it
        joined the archive (i.e. no member dominates or equals it)."""
        p = np.asarray(point, dtype=np.float64).ravel()
        for q, _ in self._points:
            if dominates(q, p) or np.array_equal(q, p):
                return False
        self._points = [(q, pl) for q, pl in self._points
                        if not dominates(p, q)]
        self._points.append((p, payload))
        return True

    def front(self, k: int | None = None) -> np.ndarray:
        """``[F, d]`` front rows, sorted lexicographically by objective
        value; ``k`` keeps the first ``k`` rows (the device archive's
        deterministic truncation rule)."""
        if not self._points:
            return np.zeros((0, 0))
        pts = np.stack([p for p, _ in self._points])
        order = np.lexsort(tuple(pts[:, j]
                                 for j in range(pts.shape[1] - 1, -1, -1)))
        pts = pts[order]
        return pts if k is None else pts[:k]

    def payloads(self, k: int | None = None) -> list:
        """Payloads aligned with :meth:`front` rows."""
        if not self._points:
            return []
        pts = np.stack([p for p, _ in self._points])
        order = np.lexsort(tuple(pts[:, j]
                                 for j in range(pts.shape[1] - 1, -1, -1)))
        out = [self._points[i][1] for i in order]
        return out if k is None else out[:k]


# ----------------------------------------------------- device fitness
def _archive_rank(obj):
    """``obj [Nc, 3]`` → index order: non-dominated rows first (exact
    duplicates keep the lowest index), then by (edp, latency, energy,
    index) — a deterministic total order, so archive truncation is
    reproducible and lane-independent. Empty slots travel as +inf rows:
    any finite row dominates them and they sort last."""
    Nc = obj.shape[0]
    le = jnp.all(obj[:, None, :] <= obj[None, :, :], axis=-1)
    lt = jnp.any(obj[:, None, :] < obj[None, :, :], axis=-1)
    dominated = jnp.any(le & lt, axis=0)
    eq = jnp.all(obj[:, None, :] == obj[None, :, :], axis=-1)
    idx = jnp.arange(Nc)
    dup = jnp.any(eq & (idx[:, None] < idx[None, :]), axis=0)
    bad = (dominated | dup).astype(jnp.int32)
    return jnp.lexsort((idx, obj[:, 2], obj[:, 1], obj[:, 0], bad))


@functools.lru_cache(maxsize=None)
def _fitness_one(batch: int, redistribution: bool, async_exec: bool,
                 energy_mode: str, congestion: str, smooth: bool):
    """The fused single-candidate fitness:
    ``fit(cp, cd, seg_overhead, Px, Py, co, rd, diag, seg)`` → ``[3]``
    objective vector (OBJECTIVES order). ``cp``/``cd`` are the plain and
    diagonal-mesh constant bundles; ``diag`` selects (hard, search) or
    interpolates (``smooth=True``, the differentiable relaxation used by
    the gradient seeding — which also swaps the SGS for its
    busiest-resource lower bound, since ``fori_loop`` scheduling has no
    useful gradient)."""

    def fit(cp, cd, seg_overhead, Px, Py, co, rd, diag, seg):
        n = Px.shape[0]
        if smooth:
            c = {k: ((1.0 - diag) * cp[k] + diag * cd[k]
                     if k in DIAG_KEYS else cp[k]) for k in cp}
        else:
            c = {k: (jnp.where(diag > 0.5, cd[k], cp[k])
                     if k in DIAG_KEYS else cp[k]) for k in cp}
        out = _eval_single(c, Px, Py, co, rd,
                           redistribution=redistribution,
                           async_exec=async_exec, energy_mode=energy_mode,
                           congestion=congestion, smooth=smooth)
        # Segment merge: seg[i] opens a boundary after op i (the last
        # op's bit is ignored), ops map to segment slots by cumulative
        # boundary count, and a one-hot matmul folds per-op phases into
        # per-slot (t_in, t_comp, t_out) durations. Empty slots are
        # zero-duration jobs — harmless to the SGS.
        notlast = jnp.concatenate(
            [jnp.ones((n - 1,), dtype=Px.dtype),
             jnp.zeros((1,), dtype=Px.dtype)])
        b = seg * notlast
        seg_id = cumsum_seq(jnp.concatenate(
            [jnp.zeros((1,), dtype=Px.dtype), b[:-1]]))
        onehot = (seg_id[:, None] == jnp.arange(n)[None, :]).astype(
            Px.dtype)
        phases = jnp.stack([out["t_in"], out["t_comp"], out["t_out"]],
                           axis=-1)                        # [n, 3]
        slot = onehot.T @ phases                           # [n, 3]
        active = jnp.sign(onehot.sum(axis=0))
        slot = slot + (seg_overhead * active)[:, None] * jnp.asarray(
            [1.0, 0.0, 0.0], dtype=phases.dtype)
        dur = slot.reshape(3 * n)
        if smooth:
            # Busiest-resource lower bound — exact when one resource
            # saturates, differentiable everywhere.
            comm = dur[0::3].sum() + dur[2::3].sum()
            comp = dur[1::3].sum()
            makespan = jnp.maximum(jnp.maximum(batch * comm, batch * comp),
                                   dur.sum())
        else:
            makespan = sgs_instance(3 * n, batch, with_starts=False)(
                dur, chain_priorities_jnp(dur))
        lat = makespan / float(batch)
        energy = out["energy"]
        return jnp.stack([energy * lat, lat, energy])

    return fit


@functools.lru_cache(maxsize=None)
def _chunk_inner(elite: int, tournament: int, freeze_redist: bool,
                 obj_idx: int, batch: int, redistribution: bool,
                 async_exec: bool, energy_mode: str, congestion: str):
    """Unjitted ``vmap(scan(generation-step))`` per static signature —
    the shard_map target of the sharded sweep fabric. Call as
    ``fn(cp, cd, win, hp, carry, keys)`` with consts/window/carry
    stacked on a leading island axis and ``keys [L, 2]`` shared across
    islands (§10 rule: islands differ through their landscape, not
    their draws, so a point's trajectory is grid-independent)."""
    vfit = jax.vmap(
        _fitness_one(batch, redistribution, async_exec, energy_mode,
                     congestion, False),
        in_axes=(None, None, None, 0, 0, 0, 0, 0, 0))

    def step(cp, cd, win, hp, carry, key):
        (Px, Py, co, rd, dg, sg,
         aobj, aPx, aPy, aco, ard, adg, asg,
         best_obj, best_vec, bPx, bPy, bco, brd, bdg, bsg,
         flat, steps) = carry
        pop, n, X = Px.shape
        Y = Py.shape[2]
        K = aobj.shape[0]
        done = (flat >= hp["patience"]) & (steps > 0)

        # ------------------------------------------------ fused fitness
        objs = vfit(cp, cd, hp["seg_overhead"],
                    Px, Py, co, rd, dg, sg)                # [P, 3]
        fit = objs[:, obj_idx]
        order = jnp.argsort(fit)
        gi = order[0]
        gen_best = fit[gi]
        improved = gen_best < best_obj * (1.0 - 1e-4)
        n_flat = jnp.where(improved, 0, flat + 1)
        better = gen_best < best_obj
        n_best_obj = jnp.where(better, gen_best, best_obj)
        n_best_vec = jnp.where(better, objs[gi], best_vec)
        n_bPx = jnp.where(better, Px[gi], bPx)
        n_bPy = jnp.where(better, Py[gi], bPy)
        n_bco = jnp.where(better, co[gi], bco)
        n_brd = jnp.where(better, rd[gi], brd)
        n_bdg = jnp.where(better, dg[gi], bdg)
        n_bsg = jnp.where(better, sg[gi], bsg)

        # ------------------------------------------- Pareto archive merge
        cobj = jnp.concatenate([aobj, objs])               # [K+P, 3]
        keep = _archive_rank(cobj)[:K]
        n_aobj = cobj[keep]
        merge = lambda arch, gene: jnp.concatenate([arch, gene])[keep]
        n_aPx, n_aPy = merge(aPx, Px), merge(aPy, Py)
        n_aco, n_ard = merge(aco, co), merge(ard, rd)
        n_adg, n_asg = merge(adg, dg), merge(asg, sg)

        # ------------------------------------- selection + crossover
        Q = pop - elite
        kt, km, kv = random.split(key, 3)
        ut = random.uniform(kt, (2, Q, tournament))
        um = random.uniform(km, (10, Q, n))
        uv = random.uniform(kv, (4, MOVE_ATTEMPTS, Q, n))

        def tourney(u):
            idx = jnp.floor(u * pop).astype(jnp.int32)
            return idx[jnp.arange(Q), jnp.argmin(fit[idx], axis=1)]

        a = tourney(ut[0])
        b = tourney(ut[1])
        gate = um[0, :, 0] < hp["p_crossover"]
        mask = gate[:, None] & (um[1] < 0.5)
        cPx = jnp.where(mask[..., None], Px[b], Px[a])
        cPy = jnp.where(mask[..., None], Py[b], Py[a])
        cco = jnp.where(mask, co[b], co[a])
        crd = jnp.where(mask, rd[b], rd[a])
        csg = jnp.where(mask, sg[b], sg[a])
        cdg = jnp.where(gate & (um[7, :, 0] < 0.5), dg[b], dg[a])

        # -------------------------------------------------- mutations
        cPx = _move_units(uv[0:2], cPx, cp["R"], win["lo_x"],
                          win["hi_x"], um[2] < hp["p_mutate_partition"])
        cPy = _move_units(uv[2:4], cPy, cp["C"], win["lo_y"],
                          win["hi_y"], um[3] < hp["p_mutate_partition"])
        mutc = um[4] < hp["p_mutate_collector"]
        cco = jnp.where(
            mutc, jnp.floor(um[5] * Y).astype(cco.dtype), cco)
        if not freeze_redist:
            mutr = um[6] < hp["p_mutate_redist"]
            crd = jnp.where(mutr, 1.0 - crd, crd)
        mutd = um[8, :, 0] < hp["p_mutate_diag"]
        cdg = jnp.where(mutd, 1.0 - cdg, cdg)
        notlast = jnp.concatenate(
            [jnp.ones((n - 1,), dtype=sg.dtype),
             jnp.zeros((1,), dtype=sg.dtype)])
        muts = (um[9] < hp["p_mutate_seg"]) & (notlast > 0)
        csg = jnp.where(muts, 1.0 - csg, csg) * notlast

        el = order[:elite]
        new = (
            jnp.concatenate([Px[el], cPx]),
            jnp.concatenate([Py[el], cPy]),
            jnp.concatenate([co[el], cco]),
            jnp.concatenate([rd[el], crd]),
            jnp.concatenate([dg[el], cdg]),
            jnp.concatenate([sg[el], csg]),
            n_aobj, n_aPx, n_aPy, n_aco, n_ard, n_adg, n_asg,
            n_best_obj, n_best_vec, n_bPx, n_bPy, n_bco, n_brd,
            n_bdg, n_bsg, n_flat, steps + 1,
        )
        # Freeze done islands (§10: early-stopped islands must report
        # exactly what a solo early-stopped run would).
        carry = jax.tree_util.tree_map(
            lambda old, upd: jnp.where(done, old, upd), carry, new)
        return carry, (carry[_BEST_OBJ], carry[_FLAT])

    def chunk(cp, cd, win, hp, carry, keys):
        def body(c, k):
            return step(cp, cd, win, hp, c, k)
        return lax.scan(body, carry, keys)

    return jax.vmap(chunk, in_axes=(0, 0, 0, None, 0, None))


@functools.lru_cache(maxsize=None)
def _chunk_fn(*statics):
    """One compiled ``vmap(scan(step))`` per static signature."""
    return jax.jit(_chunk_inner(*statics))


# ------------------------------------------------- gradient seeding
def _hw_pair(hw: HWConfig) -> tuple[HWConfig, HWConfig]:
    return (dataclasses.replace(hw, diagonal_links=False),
            dataclasses.replace(hw, diagonal_links=True))


def _consts_pair(task: Task, hw: HWConfig, options: EvalOptions):
    """(plain, diagonal) constant bundles + the plain Evaluator. Raises
    if the two meshes diverge outside DIAG_KEYS — the diag gene's
    select/interpolate contract."""
    if task.group_shape[0]:
        raise ValueError(
            f"co-search cannot take uneven grouped GEMMs ({task.name}): "
            f"its gradient seeding relaxes tile counts that the grouped "
            f"term computes per group; use solve_grid(method='ga')")
    hw_p, hw_d = _hw_pair(hw)
    evp = Evaluator(task, hw_p, options, backend="numpy")
    evd = Evaluator(task, hw_d, options, backend="numpy")
    cp, cd = evp.consts(), evd.consts()
    for k in cp:
        if k in DIAG_KEYS:
            continue
        if not np.array_equal(np.asarray(cp[k]), np.asarray(cd[k])):
            raise RuntimeError(
                f"diagonal-link mesh changed const {k!r} outside "
                f"DIAG_KEYS — the co-search diag gene cannot select it")
    return cp, cd, evp


@functools.lru_cache(maxsize=None)
def _descend_fn(batch: int, redistribution: bool, async_exec: bool,
                energy_mode: str, oi: int, steps: int):
    """One compiled vmapped projected-gradient descent per static
    signature. Rebuilding (and therefore re-jitting) the descent inside
    every :func:`gradient_seeds` call cost ~1.2 s of warm wall-clock per
    island — more than the evolution itself — so the jit wrapper is
    cached here and shape-specializes per (starts, n, X, Y) like any
    jitted function."""
    fit = _fitness_one(batch, redistribution, async_exec, energy_mode,
                       "regime", True)

    def loss(p, cpj, cdj, so, Mj, Nj, cov, rdv, sgv):
        Px = jax.nn.softmax(p["lx"], axis=-1) * Mj
        Py = jax.nn.softmax(p["ly"], axis=-1) * Nj
        w = jax.nn.sigmoid(p["t"])
        return fit(cpj, cdj, so, Px, Py, cov, rdv, w, sgv)[oi]

    def descend(p0, cpj, cdj, so, Mj, Nj, cov, rdv, sgv, lr):
        def body(_, p):
            g = jax.grad(loss)(p, cpj, cdj, so, Mj, Nj, cov, rdv, sgv)
            return jax.tree_util.tree_map(
                lambda x, gg: x - lr * gg
                / (jnp.max(jnp.abs(gg)) + 1e-30), p, g)
        return lax.fori_loop(0, steps, body, p0)

    return jax.jit(jax.vmap(descend, in_axes=(0,) + (None,) * 9))


def gradient_seeds(task: Task, hw: HWConfig, options: EvalOptions,
                   objective: str, cfg: CoSearchConfig
                   ) -> list[tuple[Partition, bool]]:
    """Projected-gradient genome proposals (deduplicated), deterministic
    in ``cfg.seed``: relax the partition lattice to a simplex
    (``softmax(logits) * M``) and the diag gene to a sigmoid, descend
    the smooth fused fitness for ``cfg.seed_steps`` fixed steps from
    ``cfg.seed_starts`` jittered starts (per-leaf max-normalized steps,
    ``lr = cfg.seed_lr``), then round through
    :func:`repro.core.workload.clamp_partition_to_domain`. The smooth
    objective always runs the regime congestion path — the flow
    netsim's ``while_loop`` is not reverse-differentiable — which is
    fine for a *seed*: the search itself scores the requested model."""
    if cfg.seed_starts < 1 or cfg.seed_steps < 1:
        return []
    opts = dataclasses.replace(options, congestion="regime")
    cp, cd, evp = _consts_pair(task, hw, opts)
    n, X, Y = len(task), hw.X, hw.Y
    Mv = np.asarray(evp.M, dtype=np.float64)
    Nv = np.asarray(evp.N, dtype=np.float64)
    co = np.full(n, Y // 2, dtype=np.float64)
    rd = (np.asarray(evp.chain_valid, dtype=np.float64)
          if opts.redistribution else np.zeros(n))
    sg = np.ones(n)
    descend = _descend_fn(int(cfg.batch), bool(opts.redistribution),
                          bool(opts.async_exec), opts.energy_mode,
                          OBJECTIVES.index(objective),
                          int(cfg.seed_steps))
    S = int(cfg.seed_starts)

    with x64():
        cpj = {k: jnp.asarray(v) for k, v in cp.items()}
        cdj = {k: jnp.asarray(v) for k, v in cd.items()}
        cov = jnp.asarray(co)
        rdv = jnp.asarray(rd)
        sgv = jnp.asarray(sg)
        so = jnp.asarray(float(cfg.seg_overhead))
        Mj = jnp.asarray(Mv)[:, None]
        Nj = jnp.asarray(Nv)[:, None]

        k1, k2, k3 = random.split(random.PRNGKey(cfg.seed), 3)
        p0 = {
            "lx": 0.5 * random.normal(k1, (S, n, X), dtype=jnp.float64),
            "ly": 0.5 * random.normal(k2, (S, n, Y), dtype=jnp.float64),
            "t": random.normal(k3, (S,), dtype=jnp.float64),
        }
        # Start 0 descends from the neutral point (uniform simplex,
        # diag 0.5) — the relaxed analogue of the uniform partition.
        p0 = {k: v.at[0].set(0.0) for k, v in p0.items()}
        pT = descend(p0, cpj, cdj, so, Mj, Nj, cov, rdv, sgv,
                     jnp.asarray(float(cfg.seed_lr)))
        Pxs = np.asarray(jax.nn.softmax(pT["lx"], axis=-1) * Mj)
        Pys = np.asarray(jax.nn.softmax(pT["ly"], axis=-1) * Nj)
        ws = np.asarray(jax.nn.sigmoid(pT["t"]))

    seeds: list[tuple[Partition, bool]] = []
    seen: set = set()
    for s in range(S):
        part = Partition(np.rint(Pxs[s]).astype(np.int64),
                         np.rint(Pys[s]).astype(np.int64),
                         co.astype(np.int64))
        part = clamp_partition_to_domain(part, task, X, Y, hw.R, hw.C,
                                         cfg.slack)
        dg = bool(ws[s] > 0.5)
        key = (part.Px.tobytes(), part.Py.tobytes(), dg)
        if key not in seen:
            seen.add(key)
            seeds.append((part, dg))
    return seeds


def miqp_anchor(task: Task, hw: HWConfig, options: EvalOptions,
                objective: str = "edp",
                cfg: CoSearchConfig | None = None) -> Partition:
    """The best projected-gradient proposal, as a lattice anchor for the
    MIQP enumeration (``miqp_jax._Space(anchor=...)``): candidate sets
    re-order (and, under a cap, prune) around the proposal instead of
    the uniform split. Falls back to the uniform partition when seeding
    is disabled."""
    cfg = cfg or CoSearchConfig()
    seeds = gradient_seeds(task, hw, options, objective, cfg)
    if not seeds:
        return clamp_partition_to_domain(
            uniform_partition(task, hw.X, hw.Y), task, hw.X, hw.Y,
            hw.R, hw.C, cfg.slack)
    return seeds[0][0]


# --------------------------------------------------------- entry points
def _init_island(task: Task, hw: HWConfig, options: EvalOptions,
                 cfg: CoSearchConfig, seeds):
    """Host population init (seeded by ``cfg.seed`` alone — grid-
    position-independent, the §10 rule): the shared GA init for the
    partition genes plus the co-search genes. Row 0 = uniform partition
    on the plain mesh / one segment; row 1 = uniform on the diagonal
    mesh / per-op segments — elitism floors the search at both
    separate-pass baselines. Gradient seeds fill rows 2.. up to
    ``cfg.seed_fraction``."""
    pop = cfg.population
    n, Y = len(task), hw.Y
    rng = np.random.default_rng(cfg.seed)
    Px, Py, coll, redist = _random_population_vec(rng, task, hw, cfg, pop)
    dg = (rng.random(pop) < 0.5).astype(np.float64)
    sg = (rng.random((pop, n)) < 0.5).astype(np.float64)
    sg[:, -1] = 0.0
    dg[0], dg[1] = 0.0, 1.0
    sg[0] = 0.0
    sg[1, :-1] = 1.0
    # Row 1 re-uses row 0's uniform partition so both mesh variants
    # start from the separate-pass baselines' LS genome.
    Px[1], Py[1], coll[1], redist[1] = Px[0], Py[0], coll[0], redist[0]
    k = min(len(seeds), int(round(cfg.seed_fraction * pop)), pop - 2)
    for j in range(k):
        part, diag = seeds[j]
        row = 2 + j
        Px[row], Py[row] = part.Px, part.Py
        coll[row] = part.collectors
        dg[row] = float(diag)
        sg[row, :-1], sg[row, -1] = 1.0, 0.0
    return Px, Py, coll, redist, dg, sg


def cosearch_islands(
    tasks: Sequence[Task],
    hws: Sequence[HWConfig],
    options: EvalOptions,
    objective: str,
    cfg: CoSearchConfig,
    devices: str | None = None,
    seeds: Sequence[Sequence[tuple[Partition, bool]]] | None = None,
) -> list[CoSearchResult]:
    """Evolve one joint search per (task, hw) island through a single
    compiled call (islands must share a shape signature —
    :func:`repro.core.sweep.cosearch_sweep` groups). ``hws`` entries are
    normalized to their plain-mesh variant internally: the diag gene
    *searches* the link axis, so a point's result is independent of the
    incoming ``diagonal_links`` flag. ``seeds=None`` computes
    projected-gradient proposals per island (``cfg.seed_fraction == 0``
    disables); pass explicit per-island seed lists (possibly empty) to
    override — e.g. the cold-start arm of a seeding experiment.

    ``devices`` (default ``cfg.devices``) shards the island axis via
    :mod:`repro.core.sweep_shard`; results are bitwise identical to the
    single-device path."""
    from . import sweep_shard

    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"one of {OBJECTIVES}")
    G = len(tasks)
    assert G == len(hws) and G > 0
    pop = cfg.population
    elite = min(cfg.elite, pop - 1)
    K = int(cfg.archive_size)

    pairs = [_consts_pair(t, dataclasses.replace(h, diagonal_links=False),
                          options) for t, h in zip(tasks, hws)]
    keys0 = pairs[0][0].keys()
    cp = {k: np.stack([p[0][k] for p in pairs]) for k in keys0}
    cd = {k: np.stack([p[1][k] for p in pairs]) for k in keys0}
    evs = [p[2] for p in pairs]

    from .workload import partition_domain
    win = {"lo_x": [], "hi_x": [], "lo_y": [], "hi_y": []}
    inits = []
    for g, (t, h) in enumerate(zip(tasks, hws)):
        lo, hi = partition_domain(t, h.X, h.Y, h.R, h.C, cfg.slack)
        win["lo_x"].append(lo[:, 0])
        win["hi_x"].append(hi[:, 0])
        win["lo_y"].append(lo[:, 1])
        win["hi_y"].append(hi[:, 1])
        if seeds is not None:
            sd = list(seeds[g])
        elif cfg.seed_fraction > 0:
            sd = gradient_seeds(t, h, options, objective, cfg)
        else:
            sd = []
        inits.append(_init_island(t, h, options, cfg, sd))
    win = {k: np.stack(v).astype(np.float64) for k, v in win.items()}
    hp = {
        "p_crossover": float(cfg.p_crossover),
        "p_mutate_partition": float(cfg.p_mutate_partition),
        "p_mutate_collector": float(cfg.p_mutate_collector),
        "p_mutate_redist": float(cfg.p_mutate_redist),
        "p_mutate_diag": float(cfg.p_mutate_diag),
        "p_mutate_seg": float(cfg.p_mutate_seg),
        "patience": int(cfg.patience),
        "seg_overhead": float(cfg.seg_overhead),
    }
    statics = (elite, int(cfg.tournament), bool(cfg.freeze_redist),
               OBJECTIVES.index(objective), int(cfg.batch),
               bool(options.redistribution), bool(options.async_exec),
               options.energy_mode, options.congestion)
    if devices is None:
        devices = getattr(cfg, "devices", "single")
    if sweep_shard.resolve_devices(devices, G) == "sharded":
        inner = _chunk_inner(*statics)

        def fn(cp, cd, win, hp, carry, keys):
            return sweep_shard.sharded_grid_call(
                inner, (cp, cd, win, hp, carry, keys),
                (True, True, True, False, True, False), G)
    else:
        fn = _chunk_fn(*statics)

    n = len(tasks[0])
    X, Y = hws[0].X, hws[0].Y
    with x64():
        cpj = {k: jnp.asarray(v) for k, v in cp.items()}
        cdj = {k: jnp.asarray(v) for k, v in cd.items()}
        win_j = {k: jnp.asarray(v) for k, v in win.items()}
        f8 = lambda a: jnp.asarray(a, dtype=jnp.float64)
        carry = (
            f8(np.stack([i[0] for i in inits])),
            f8(np.stack([i[1] for i in inits])),
            f8(np.stack([i[2] for i in inits])),
            f8(np.stack([i[3] for i in inits])),
            f8(np.stack([i[4] for i in inits])),
            f8(np.stack([i[5] for i in inits])),
            jnp.full((G, K, 3), jnp.inf, dtype=jnp.float64),
            jnp.zeros((G, K, n, X), dtype=jnp.float64),
            jnp.zeros((G, K, n, Y), dtype=jnp.float64),
            jnp.zeros((G, K, n), dtype=jnp.float64),
            jnp.zeros((G, K, n), dtype=jnp.float64),
            jnp.zeros((G, K), dtype=jnp.float64),
            jnp.zeros((G, K, n), dtype=jnp.float64),
            jnp.full((G,), jnp.inf, dtype=jnp.float64),
            jnp.full((G, 3), jnp.inf, dtype=jnp.float64),
            jnp.zeros((G, n, X), dtype=jnp.float64),
            jnp.zeros((G, n, Y), dtype=jnp.float64),
            jnp.zeros((G, n), dtype=jnp.float64),
            jnp.zeros((G, n), dtype=jnp.float64),
            jnp.zeros((G,), dtype=jnp.float64),
            jnp.zeros((G, n), dtype=jnp.float64),
            jnp.zeros((G,), dtype=jnp.int32),
            jnp.zeros((G,), dtype=jnp.int32),
        )
        key = random.PRNGKey(cfg.seed)
        best_hist = []
        gens_left = int(cfg.generations)
        chunk_len = max(1, min(int(cfg.patience), gens_left))
        while gens_left > 0:
            L = min(chunk_len, gens_left)
            key, sub = random.split(key)
            keys = random.split(sub, L)
            carry, (yb, _yf) = fn(cpj, cdj, win_j, hp, carry, keys)
            best_hist.append(np.asarray(yb))
            gens_left -= L
            if (np.asarray(carry[_FLAT]) >= cfg.patience).all():
                break

        host = [np.asarray(leaf) for leaf in carry]
    best_all = np.concatenate(best_hist, axis=1)            # [G, T]

    (aobj, aPx, aPy, aco, ard, adg, asg) = host[6:13]
    best_obj, best_vec = host[13], host[14]
    bPx, bPy, bco, brd, bdg, bsg = host[15:21]
    steps = host[22]

    results = []
    for g in range(G):
        T = int(steps[g])
        part = Partition(np.rint(bPx[g]).astype(np.int64),
                         np.rint(bPy[g]).astype(np.int64),
                         np.rint(bco[g]).astype(np.int64))
        part.validate(tasks[g])
        finite = np.isfinite(aobj[g][:, 0])
        fo = aobj[g][finite]
        mask = pareto_mask(fo)
        order = np.lexsort((fo[mask][:, 2], fo[mask][:, 1],
                            fo[mask][:, 0]))
        sel = np.flatnonzero(finite)[mask][order]
        seg_best = bsg[g] > 0.5
        if n:
            seg_best[-1] = False
        front_seg = asg[g][sel] > 0.5
        if n:
            front_seg[:, -1] = False
        results.append(CoSearchResult(
            partition=part,
            redist_mask=(brd[g] > 0.5) & evs[g].chain_valid,
            diagonal=bool(bdg[g] > 0.5),
            seg_mask=seg_best,
            objective=float(best_obj[g]),
            edp=float(best_vec[g][0]),
            latency=float(best_vec[g][1]),
            energy=float(best_vec[g][2]),
            front={
                "edp": aobj[g][sel][:, 0].copy(),
                "latency": aobj[g][sel][:, 1].copy(),
                "energy": aobj[g][sel][:, 2].copy(),
                "Px": aPx[g][sel].copy(),
                "Py": aPy[g][sel].copy(),
                "collectors": aco[g][sel].copy(),
                "redist": ard[g][sel] > 0.5,
                "diag": adg[g][sel] > 0.5,
                "seg": front_seg,
            },
            history=best_all[g, :T].copy(),
            evaluations=T * pop,
        ))
    return results


def run_cosearch(task: Task, hw: HWConfig, objective: str = "edp",
                 options: EvalOptions | None = None,
                 cfg: CoSearchConfig | None = None) -> CoSearchResult:
    """Single-point entry: the ``G=1`` case of :func:`cosearch_islands`
    (same executable, so the result matches the island path exactly)."""
    return cosearch_islands([task], [hw], options or EvalOptions(),
                            objective, cfg or CoSearchConfig())[0]
