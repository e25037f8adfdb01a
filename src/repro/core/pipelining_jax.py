"""JAX backend for the RCPSP pipeliner — batched list scheduling
(DESIGN.md §13).

The cross-sample pipelining DAG of Sec. 5.4 is *regular*: every sample of
a batch emits the same (in, comp, out) chain, so a whole instance is a
dense ``[batch, n_ops, 3]`` duration tensor and the serial heapq SGS of
:mod:`repro.core.pipelining` collapses into array form:

  * **Priorities** — a chain job's only successor is the next chain job,
    so the critical-path walk is a reversed cumulative sum, identical for
    every sample (:func:`repro.core.pipelining.chain_priorities`; computed
    on host so both backends compare bit-identical floats on ties).
  * **Ready set = per-sample frontier** — scheduling a job makes its
    chain successor ready immediately, so the heap always holds exactly
    one entry (the next unscheduled chain position) per unfinished
    sample. The SGS step is therefore an ``argmax`` of priority over the
    ``[batch]`` frontier vector (ties → smallest jid, the heap's
    tie-break), dispatched onto its unit resource — ``batch × 3n`` such
    steps driven by ``lax.fori_loop`` schedule the whole instance.
  * **Grids** — ``vmap`` over a leading grid axis batches every instance
    sharing (n_ops, batch) — whole (workload × batch × segment-variant)
    sweeps run through ONE compiled call per shape group
    (:func:`repro.core.sweep.pipeline_sweep` does the grouping); a solo
    call is the ``G=1`` case of the same executable, so solo == batched
    exactly (the §9 cache invariant).

Exactness: every arithmetic op (max, add) matches the serial engine
bit-for-bit — the contract is *bit-identical* makespans and start times,
stronger than the §8 evaluator backends' rtol-1e-9 parity
(``tests/test_core_pipelining_engines.py`` enforces it).

All entry points run under the :func:`repro.core.x64.x64` scope (same
float64 rule and leak-containment scoping as
:mod:`repro.core.netsim_jax`).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .pipelining import chain_priorities
from .x64 import cumsum_seq, x64

__all__ = ["schedule_batch", "sgs_instance", "chain_priorities_jnp"]


def chain_priorities_jnp(dur_flat):
    """Traced equivalent of :func:`repro.core.pipelining.chain_priorities`
    (reversed cumulative sum — a chain job's critical path). Used by
    callers that build priorities *inside* a jitted objective
    (:mod:`repro.core.cosearch`); :func:`schedule_batch` keeps computing
    them on host so the serial-engine bit-parity contract is pinned to
    one accumulation order. The sequential scan adds in ``np.cumsum``'s
    order, so wherever float64 is IEEE (not on a TPU, which emulates it)
    these priorities are bitwise equal to the host ones."""
    return cumsum_seq(dur_flat[::-1])[::-1]


@functools.lru_cache(maxsize=None)
def sgs_instance(L: int, B: int, with_starts: bool = True):
    """Traced single-instance SGS per (chain length, batch) signature:
    ``one(dur [L], prio [L])`` → ``(makespan, starts [B, L])``, or just
    the makespan with ``with_starts=False`` (skips the per-step start
    scatter — the form embedded in fused objectives such as the
    co-search fitness, DESIGN.md §16). Durations/priorities are data, so
    one instance serves every same-shape schedule; cached so wrappers
    (vmap/jit/shard_map) key on a stable function identity."""
    # Chain resource pattern (in, comp, out) per op: 0 = comm, 1 = comp.
    # Held as numpy and lifted per trace — the instance may be *built*
    # inside an enclosing trace (the co-search fused fitness), and a
    # cached closure over trace-born jnp arrays would leak tracers into
    # later traces.
    res_np = np.tile(np.array([0, 1, 0], dtype=np.int32), L // 3)
    base_np = np.arange(B, dtype=np.int32) * L

    def one(dur, prio):
        res = jnp.asarray(res_np)
        sample_base = jnp.asarray(base_np)

        def step(_, state):
            ptr, ready, free = state[:3]
            active = ptr < L
            pr = jnp.where(active, prio[jnp.minimum(ptr, L - 1)], -jnp.inf)
            # Highest-priority ready job; ties resolve to the smallest
            # jid (= sample*L + ptr), exactly like the serial heap.
            cand = jnp.where(active & (pr == jnp.max(pr)),
                             sample_base + ptr, B * L)
            s = jnp.argmin(cand)
            p = ptr[s]
            r = res[p]
            t0 = jnp.maximum(ready[s], free[r])
            t1 = t0 + dur[p]
            out = (ptr.at[s].add(1), ready.at[s].set(t1),
                   free.at[r].set(t1))
            if with_starts:
                out = out + (state[3].at[s, p].set(t0),)
            return out

        init = (jnp.zeros(B, dtype=jnp.int32),
                jnp.zeros(B, dtype=jnp.float64),
                jnp.zeros(2, dtype=jnp.float64))
        if with_starts:
            init = init + (jnp.zeros((B, L), dtype=jnp.float64),)
        state = lax.fori_loop(0, B * L, step, init)
        # Resource frees only ever ratchet up to the latest finish, so
        # the makespan is their max (0.0 when no job ran — serial init).
        if with_starts:
            return jnp.max(state[2]), state[3]
        return jnp.max(state[2])

    return one


@functools.lru_cache(maxsize=None)
def _sched_inner(L: int, B: int):
    """Unjitted ``vmap(instance)`` per (chain length, batch) signature —
    durations/priorities as data; doubles as the shard_map target of the
    sharded sweep fabric (DESIGN.md §15)."""
    return jax.vmap(sgs_instance(L, B))


@functools.lru_cache(maxsize=None)
def _sched_fn(L: int, B: int):
    """One compiled batched SGS per (chain length, batch) signature, so
    every same-shape grid point shares the executable."""
    return jax.jit(_sched_inner(L, B))


def schedule_batch(segments_grid: np.ndarray, batch: int,
                   devices: str = "single") -> dict[str, np.ndarray]:
    """Batched list scheduling: ``segments_grid [G, n, 3]`` per-op
    (t_in, t_comp, t_out) durations for ``G`` same-shape grid points →
    ``{"makespan": [G], "starts": [G, batch, 3n]}`` (``starts[g, s, p]``
    = start of sample ``s``'s p-th chain job, jid ``s*3n + p`` in
    :func:`repro.core.pipelining.build_jobs` order). One compiled call
    per (n, batch) signature covers the whole group; ``devices``
    (DESIGN.md §15) shards the grid axis across local devices with
    bit-identical schedules."""
    from . import sweep_shard

    seg = np.asarray(segments_grid, dtype=np.float64)
    G, n = seg.shape[0], seg.shape[1]
    L = 3 * n
    dur = np.maximum(seg.reshape(G, L) if L else np.zeros((G, 0)), 0.0)
    if L == 0 or batch == 0:
        return {"makespan": np.zeros(G), "starts": np.zeros((G, batch, L))}
    prio = np.stack([chain_priorities(dur[g]) for g in range(G)])
    with x64():
        args = (jnp.asarray(dur), jnp.asarray(prio))
        if sweep_shard.resolve_devices(devices, G) == "sharded":
            ms, starts = sweep_shard.sharded_grid_call(
                _sched_inner(L, int(batch)), args, (True, True), G)
        else:
            ms, starts = _sched_fn(L, int(batch))(*args)
        return {"makespan": np.asarray(ms), "starts": np.asarray(starts)}
