"""Device-resident GA engine — jit-fused evolution loop (DESIGN.md §10).

The python/numpy engines in :mod:`repro.core.ga` pay a host↔device round
trip per generation (fitness on device, genetic operators on host). This
module keeps the whole genome tensor on device and fuses fitness +
tournament selection + per-op uniform crossover + sum-preserving unit-move
mutation + collector/redist resampling into ONE jitted generation step,
driven by ``lax.scan`` in chunks of ~``patience`` generations:

  * **Genome layout on device** — ``Px [G, P, n, X]``, ``Py [G, P, n, Y]``,
    ``collectors [G, P, n]``, ``redist [G, P, n]``, all float64 (fitness
    needs float64 anyway and unit moves are exact in it). ``G`` is the
    *island* axis: :func:`solve_islands` evolves many same-shape sweep
    points' searches through one compiled call (``jit(vmap(scan(step)))``);
    a single :func:`run_ga_jax` search is the ``G=1`` special case of the
    same executable, so per-point results are identical whether a point is
    solved alone or inside a grid (the sweep-cache invariant).
  * **Chunked early stop** — the scan runs ``min(patience, remaining)``
    generations per compiled call and only then syncs the ``flat`` counters
    to the host, so early stopping costs one device→host transfer per
    ~``patience`` generations instead of one per generation. Islands whose
    ``flat`` counter reached ``patience`` freeze: the step computes the next
    epoch but keeps the old carry, so a done island's history/best/
    evaluations are exactly what a solo early-stopped run would report.
  * **RNG** — all randomness is ``jax.random`` (host init excepted: the
    initial population comes from the shared numpy init in
    :func:`repro.core.ga._random_population_vec`, so both vectorized
    engines start identically). numpy↔jax trajectory parity is therefore
    impossible; the cross-engine contract is property-based invariants plus
    fixed-seed solution-quality equivalence (DESIGN.md §10,
    ``tests/test_core_ga_engines.py``).

Static (compile-time) knobs: population/op/grid shapes, ``elite``,
``tournament``, ``freeze_redist``, the objective key, and the
:class:`EvalOptions` toggles. Everything else — mutation probabilities,
``patience``, domain windows, all evaluator constants — is traced, so one
executable serves every same-shape config (same sharing rule as
:mod:`repro.core.evaluator_jax`).
"""
from __future__ import annotations

import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax, random

from ..runtime.spans import span
from .evaluator import EvalOptions, Evaluator, group_rows
from .evaluator_jax import _eval_single, _host_bytes
from .ga import MOVE_ATTEMPTS
from .hw import HWConfig
from .workload import Partition, Task, group_counts, partition_domain
from .x64 import x64

__all__ = ["run_ga_jax", "solve_islands"]

#: Objectives the fused step can minimize (keys of the evaluator output).
OBJECTIVES = ("latency", "energy", "edp")

# Carry tuple layout (all leaves carry a leading island axis under vmap):
# (Px, Py, collectors, redist, best_obj, best_Px, best_Py, best_co,
#  best_rd, flat, steps)
_BEST_OBJ, _FLAT, _STEPS = 4, 9, 10


def _move_units(u, P_, unit, lo, hi, active):
    """Device port of :func:`repro.core.ga._move_units_vec`: rejection-
    sampled sum-preserving unit moves over the whole ``[Q, n, X]`` tensor.
    Four fixed attempts (same constant as the host engines).

    ``u`` is a pre-drawn uniform block ``[2, MOVE_ATTEMPTS, Q, n]``
    (donor/receiver per attempt). Donor/receiver selection and the
    scatter update are expressed as iota-mask arithmetic rather than
    gather/one-hot ops — XLA-CPU lowers the masked form ~4× faster inside
    a vmapped scan, and it fuses with the surrounding elementwise work."""
    Q, n, X = P_.shape
    if X < 2:
        return P_
    iota = jnp.arange(X)[None, None, :]
    d_all = jnp.floor(u[0] * X).astype(jnp.int32)
    r_all = jnp.floor(u[1] * X).astype(jnp.int32)
    pending = active
    for t in range(MOVE_ATTEMPTS):
        d, r = d_all[t], r_all[t]
        dm = iota == d[..., None]
        rm = iota == r[..., None]
        dv = (P_ * dm).sum(-1)
        rv = (P_ * rm).sum(-1)
        ok = (pending & (d != r)
              & (dv - unit >= lo[None] * unit)
              & (rv + unit <= hi[None] * unit))
        delta = (rm.astype(P_.dtype) - dm.astype(P_.dtype)) * unit
        P_ = P_ + ok[..., None] * delta
        pending = pending & ~ok
    return P_


@functools.lru_cache(maxsize=None)
def _chunk_inner(elite: int, tournament: int, freeze_redist: bool,
                 objective: str, redistribution: bool, async_exec: bool,
                 energy_mode: str, congestion: str = "regime"):
    """Unjitted ``vmap(scan(generation-step))`` per static signature —
    the shard_map target of the sharded sweep fabric (DESIGN.md §15).

    Call as ``fn(consts, win, hp, carry, keys)`` with consts/win/carry
    stacked on a leading island axis and ``keys [L, 2]`` shared across
    islands (islands differ through their fitness landscape, not their
    random draws — which keeps a point's trajectory independent of which
    grid it is solved in)."""
    evalp = jax.vmap(
        functools.partial(_eval_single, redistribution=redistribution,
                          async_exec=async_exec, energy_mode=energy_mode,
                          congestion=congestion),
        in_axes=(None, 0, 0, 0, 0))

    def step(consts, win, hp, carry, key):
        (Px, Py, co, rd, best_obj, bPx, bPy, bco, brd, flat, steps) = carry
        pop, n, X = Px.shape
        Y = Py.shape[2]
        # steps > 0 mirrors the host engines' loop shape: generation 0
        # always evaluates (its history entry + best genome must exist),
        # the early-stop check runs after it — so patience <= 0 stops
        # after exactly one generation instead of freezing a zeroed
        # genome carry.
        done = (flat >= hp["patience"]) & (steps > 0)

        # ------------------------------------------------ fitness + best
        fit = evalp(consts, Px, Py, co, rd)[objective]
        order = jnp.argsort(fit)
        gi = order[0]
        gen_best = fit[gi]
        improved = gen_best < best_obj * (1.0 - 1e-4)
        n_flat = jnp.where(improved, 0, flat + 1)
        better = gen_best < best_obj
        n_best_obj = jnp.where(better, gen_best, best_obj)
        n_bPx = jnp.where(better, Px[gi], bPx)
        n_bPy = jnp.where(better, Py[gi], bPy)
        n_bco = jnp.where(better, co[gi], bco)
        n_brd = jnp.where(better, rd[gi], brd)

        # ------------------------------------- selection + crossover
        # Three batched uniform draws cover every random decision of the
        # generation — per-decision threefry calls are the dominant
        # overhead of a naive port on CPU.
        Q = pop - elite
        kt, km, kv = random.split(key, 3)
        ut = random.uniform(kt, (2, Q, tournament))
        um = random.uniform(km, (7, Q, n))
        uv = random.uniform(kv, (4, MOVE_ATTEMPTS, Q, n))

        def tourney(u):
            idx = jnp.floor(u * pop).astype(jnp.int32)
            return idx[jnp.arange(Q), jnp.argmin(fit[idx], axis=1)]

        a = tourney(ut[0])
        b = tourney(ut[1])
        mask = ((um[0, :, 0] < hp["p_crossover"])[:, None]
                & (um[1] < 0.5))
        cPx = jnp.where(mask[..., None], Px[b], Px[a])
        cPy = jnp.where(mask[..., None], Py[b], Py[a])
        cco = jnp.where(mask, co[b], co[a])
        crd = jnp.where(mask, rd[b], rd[a])

        # -------------------------------------------------- mutations
        cPx = _move_units(uv[0:2], cPx, consts["R"], win["lo_x"],
                          win["hi_x"], um[2] < hp["p_mutate_partition"])
        cPy = _move_units(uv[2:4], cPy, consts["C"], win["lo_y"],
                          win["hi_y"], um[3] < hp["p_mutate_partition"])
        mutc = um[4] < hp["p_mutate_collector"]
        cco = jnp.where(
            mutc, jnp.floor(um[5] * Y).astype(cco.dtype), cco)
        if not freeze_redist:
            mutr = um[6] < hp["p_mutate_redist"]
            crd = jnp.where(mutr, 1.0 - crd, crd)

        new = (
            jnp.concatenate([Px[order[:elite]], cPx]),
            jnp.concatenate([Py[order[:elite]], cPy]),
            jnp.concatenate([co[order[:elite]], cco]),
            jnp.concatenate([rd[order[:elite]], crd]),
            n_best_obj, n_bPx, n_bPy, n_bco, n_brd, n_flat, steps + 1,
        )
        # Freeze done islands: a finished search must report exactly what
        # a solo early-stopped run would (history length, best, counts).
        carry = jax.tree_util.tree_map(
            lambda old, upd: jnp.where(done, old, upd), carry, new)
        return carry, (carry[_BEST_OBJ], carry[_FLAT])

    def chunk(consts, win, hp, carry, keys):
        def body(c, k):
            return step(consts, win, hp, c, k)
        return lax.scan(body, carry, keys)

    return jax.vmap(chunk, in_axes=(0, 0, None, 0, None))


@functools.lru_cache(maxsize=None)
def _chunk_fn(elite: int, tournament: int, freeze_redist: bool,
              objective: str, redistribution: bool, async_exec: bool,
              energy_mode: str, congestion: str = "regime"):
    """One compiled ``vmap(scan(generation-step))`` per static
    signature — the single-device form of :func:`_chunk_inner`."""
    return jax.jit(_chunk_inner(elite, tournament, freeze_redist,
                                objective, redistribution, async_exec,
                                energy_mode, congestion))


def _init_key(task: Task, hw: HWConfig) -> tuple:
    """What :func:`repro.core.ga._random_population_vec` reads of an
    island: the op count and per-op ``M``/``N`` (uniform partition and
    Sec-6.2 window) and the grid and systolic array. Rates, links,
    chiplet classes and memory do not enter the initial population."""
    return (len(task), tuple((op.M, op.N) for op in task.ops),
            hw.X, hw.Y, hw.R, hw.C)


def solve_islands(
    tasks: Sequence[Task],
    hws: Sequence[HWConfig],
    options: EvalOptions,
    objective: str,
    cfg,
    devices: str | None = None,
    seeds: Sequence[Sequence[Partition]] | None = None,
) -> list:
    """Evolve one GA search per (task, hw) island through a single
    compiled call. All islands must share a shape signature (n_ops, X, Y,
    n_entrances) — :func:`repro.core.sweep.solve_grid` does the grouping.
    Returns one :class:`repro.core.ga.GAResult` per island, aligned with
    the inputs.

    The host initial population is built once per distinct
    (task dims, grid) key among the islands (:func:`_init_key`) and
    copied to every island with that key; islands that differ only in
    rates or links (e.g. ``diagonal_links`` x ``bw_nop`` grids) share
    one build. Results are unchanged: each island starts from exactly
    the population a solo run would build.

    ``devices`` (default: ``cfg.devices``, DESIGN.md §15) shards the
    island axis across local devices: consts/window/carry shard, the
    hyperparams and the per-generation keys replicate (keys are shared
    across islands by construction, so a shard sees exactly the draws a
    solo run would). Results are bitwise identical to the single-device
    path.

    ``seeds`` (optional, per island) warm-starts the search: island
    ``g``'s population rows ``1..`` are overwritten with the given
    :class:`Partition` proposals (row 0 keeps the uniform baseline, so a
    seeded run can never start worse than a cold one). Collector /
    redistribution genes of a seeded row keep the init's draws — seeds
    speak only to the partition lattice (e.g. the projected-gradient
    proposals of :func:`repro.core.cosearch.gradient_seeds`, DESIGN.md
    §16). ``seeds=None`` preserves the cold-start init bit-for-bit."""
    from . import sweep_shard
    from .ga import GAResult, _random_population_vec

    if objective not in OBJECTIVES:
        raise ValueError(f"unknown objective {objective!r}; "
                         f"one of {OBJECTIVES}")
    G = len(tasks)
    assert G == len(hws) and G > 0
    pop = cfg.population
    elite = min(cfg.elite, pop - 1)

    with span("ga.consts", islands=G) as sp:
        evs = [Evaluator(t, h, options, backend="numpy")
               for t, h in zip(tasks, hws)]
        keys0 = evs[0].consts().keys()
        consts = {k: np.stack([ev.consts()[k] for ev in evs])
                  for k in keys0}
        win = {"lo_x": [], "hi_x": [], "lo_y": [], "hi_y": []}
        for t, h in zip(tasks, hws):
            lo, hi = partition_domain(t, h.X, h.Y, h.R, h.C, cfg.slack)
            win["lo_x"].append(lo[:, 0])
            win["hi_x"].append(hi[:, 0])
            win["lo_y"].append(lo[:, 1])
            win["hi_y"].append(hi[:, 1])
        win = {k: np.stack(v).astype(np.float64) for k, v in win.items()}
        sp.set_metadata(bytes=_host_bytes(consts, win), **group_counts(tasks))
    with span("ga.init", islands=G, population=pop) as sp:
        # Shared host init (per-island RNG seeded by cfg.seed alone, so a
        # point's result never depends on its position in the grid). The
        # population depends on the island only through _init_key, so it
        # is built once per distinct key and shared by reference; np.stack
        # below copies it per island.
        built = {}
        inits = []
        for t, h in zip(tasks, hws):
            k = _init_key(t, h)
            if k not in built:
                built[k] = _random_population_vec(
                    np.random.default_rng(cfg.seed), t, h, cfg, pop)
            inits.append(built[k])
        sp.set_metadata(distinct=len(built))
        if seeds is not None:
            if len(seeds) != G:
                raise ValueError(f"seeds must align with islands: "
                                 f"{len(seeds)} != {G}")
            for g, props in enumerate(seeds):
                # own copies: a build is shared by every island of its key
                Px0, Py0 = inits[g][0].copy(), inits[g][1].copy()
                for j, p in enumerate(props[:pop - 1]):
                    Px0[j + 1] = p.Px
                    Py0[j + 1] = p.Py
                inits[g] = (Px0, Py0, *inits[g][2:])
    hp = {
        "p_crossover": float(cfg.p_crossover),
        "p_mutate_partition": float(cfg.p_mutate_partition),
        "p_mutate_collector": float(cfg.p_mutate_collector),
        "p_mutate_redist": float(cfg.p_mutate_redist),
        "patience": int(cfg.patience),
    }
    statics = (elite, int(cfg.tournament), bool(cfg.freeze_redist),
               objective, bool(options.redistribution),
               bool(options.async_exec), options.energy_mode,
               options.congestion)
    if devices is None:
        devices = getattr(cfg, "devices", "single")
    if sweep_shard.resolve_devices(devices, G) == "sharded":
        inner = _chunk_inner(*statics)

        def fn(consts, win, hp, carry, keys):
            # Padding replicates island 0 each chunk: a padded lane
            # evolves exactly like island 0 (same consts, same shared
            # keys), so chunk count and every real island's carry match
            # the single-device run bit-for-bit.
            return sweep_shard.sharded_grid_call(
                inner, (consts, win, hp, carry, keys),
                (True, True, False, True, False), G)
    else:
        fn = _chunk_fn(*statics)

    n = len(tasks[0])
    X, Y = hws[0].X, hws[0].Y
    with x64():
        with span("ga.to_device") as sp:
            genomes = [np.stack([i[j] for i in inits]) for j in range(4)]
            sp.set_metadata(bytes=_host_bytes(consts, win, genomes))
            consts_j = {k: jnp.asarray(v) for k, v in consts.items()}
            win_j = {k: jnp.asarray(v) for k, v in win.items()}
            f8 = lambda a: jnp.asarray(a, dtype=jnp.float64)
            carry = (
                *(f8(a) for a in genomes),
                jnp.full((G,), jnp.inf, dtype=jnp.float64),
                jnp.zeros((G, n, X), dtype=jnp.float64),
                jnp.zeros((G, n, Y), dtype=jnp.float64),
                jnp.zeros((G, n), dtype=jnp.float64),
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
            with span("ga.chunk", generations=L):
                key, sub = random.split(key)
                keys = random.split(sub, L)
                carry, (yb, _yf) = fn(consts_j, win_j, hp, carry, keys)
                best_hist.append(np.asarray(yb))            # [G, L]
                gens_left -= L
                # One device→host sync per chunk — the early-stop check.
                stop = (np.asarray(carry[_FLAT]) >= cfg.patience).all()
            if stop:
                break

    with span("ga.results") as sp:
        best_obj = np.asarray(carry[_BEST_OBJ])
        bPx, bPy, bco, brd = (np.asarray(carry[i]) for i in (5, 6, 7, 8))
        steps = np.asarray(carry[_STEPS])
        best_all = np.concatenate(best_hist, axis=1)        # [G, T]
        results = []
        for g in range(G):
            # steps[g] = generations actually evaluated; frozen tail steps
            # of the last chunk repeat the final state and are dropped.
            T = int(steps[g])
            part = Partition(np.rint(bPx[g]).astype(np.int64),
                             np.rint(bPy[g]).astype(np.int64),
                             np.rint(bco[g]).astype(np.int64))
            part.validate(tasks[g])
            results.append(GAResult(
                partition=part,
                redist_mask=(brd[g] > 0.5) & evs[g].chain_valid,
                objective=float(best_obj[g]),
                history=best_all[g, :T].copy(),
                evaluations=T * pop,
            ))
        if any(len(ev.g_idx) for ev in evs):
            sp.set_metadata(expert_fetch_excess=sum(
                fetch_excess(ev, r.partition)
                for ev, r in zip(evs, results)))
    return results


def fetch_excess(ev: Evaluator, part: Partition) -> int:
    """Group weight fetches a partition adds over one per group: Σ_x t_x
    (groups each chiplet row needs) less the groups that have rows,
    summed over the task's grouped ops."""
    r = group_rows(part.Px[ev.g_idx].astype(np.float64), ev.g_off)
    return int((r > 0).sum() - (np.diff(ev.g_off, axis=-1) > 0).sum())


def run_ga_jax(task: Task, hw: HWConfig, objective: str,
               options: EvalOptions, cfg):
    """Single-search entry point: the ``G=1`` case of
    :func:`solve_islands` (same executable, so results match the island
    path exactly)."""
    return solve_islands([task], [hw], options, objective, cfg)[0]
