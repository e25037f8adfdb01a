"""JAX backend for the flow-level netsim — jitted max-min waterfilling.

Port of the vectorized numpy engine in :mod:`repro.core.netsim`
(:func:`~repro.core.netsim.waterfill_rates` /
:func:`~repro.core.netsim.simulate_flows`) to ``lax.while_loop``, so that

  * whole (mesh × memory × placement × bandwidth) grids batch through ONE
    compiled call (:func:`simulate_pull_batch` — ``vmap`` over a leading
    grid axis, mirroring the ``evaluator``/``evaluator_jax`` contract of
    DESIGN.md §8/§11), and
  * the evaluator's ``congestion="flow"`` mode can trace the simulation
    inside its own jit (:func:`waterfill_times` is a pure traced
    function of ``(cap, incidence, bytes)``).

Shapes are the only compile-time statics: the :mod:`repro.core.topology`
link space is a pure function of (X, Y) — every memory placement /
bandwidth cell of a grid is data, not structure — so one executable
serves the entire grid. All entry points run under the
:func:`repro.core.x64.x64` scope (same float64 rule, and the same
leak-containment scoping, as :mod:`repro.core.evaluator_jax`).

Numerics note: each waterfilling iteration retires the argmin-share
bottleneck link exactly like the numpy engine, and the event loop uses
the same ``EPS_BYTES`` completion threshold — completion times agree
with both host engines to float64 round-off
(``tests/test_core_netsim.py`` enforces the three-way contract).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .netsim import EPS_BYTES, MAX_EVENTS
from .x64 import x64

__all__ = ["waterfill_rates", "waterfill_times", "simulate_pull_batch"]


def waterfill_rates(inc, cap, active):
    """Max-min fair rates (traced): ``inc [F, L]``, ``cap [L]``,
    ``active [F]`` (float 0/1) → ``(rates [F], fills)``, ``fills`` the
    int32 count of filling iterations. Progressive filling via
    ``lax.while_loop`` — at least one link retires per iteration."""
    F = inc.shape[0]

    def users_of(unfixed):
        return unfixed @ inc                              # [L]

    def cond(state):
        _, unfixed, _, _ = state
        return jnp.any(users_of(unfixed) > 0)

    def body(state):
        residual, unfixed, rates, fills = state
        users = users_of(unfixed)
        live = users > 0
        share = jnp.where(live, residual / jnp.where(live, users, 1.0),
                          jnp.inf)
        l = jnp.argmin(share)
        s = share[l]
        newly = (unfixed > 0) & (inc[:, l] > 0)
        rates = jnp.where(newly, s, rates)
        residual = jnp.maximum(
            residual - (newly.astype(inc.dtype) @ inc) * s, 0.0)
        unfixed = jnp.where(newly, 0.0, unfixed)
        return residual, unfixed, rates, fills + 1

    init = (cap, active.astype(inc.dtype), jnp.zeros(F, dtype=inc.dtype),
            jnp.asarray(0, dtype=jnp.int32))
    _, _, rates, fills = lax.while_loop(cond, body, init)
    return rates, fills


def waterfill_times(cap, inc, message_bytes):
    """Traced event-driven simulation of ``F`` concurrent flows.

    Line-for-line port of :func:`repro.core.netsim.simulate_flows`:
    each event solves the waterfilling fixed point, advances to the next
    completion, retires finished flows. Returns ``(latency, done [F],
    link_bytes [L], events, fills)``: the last two are int32 counts of
    the event loop's iterations and of the filling iterations summed over
    its events, so a vmapped caller can compare each lane's work with the
    batch's lockstep count (a lane whose flows are all empty counts 0 and
    0). Usable inside an outer jit/vmap (the evaluator's flow mode vmaps
    it over the op axis)."""
    F, L = inc.shape
    bytes0 = message_bytes.astype(inc.dtype)

    def cond(state):
        bytes_left, _, _, _, it, _ = state
        return jnp.any(bytes_left > EPS_BYTES) & (it < MAX_EVENTS)

    def body(state):
        bytes_left, t, done, link_bytes, it, fills = state
        active = bytes_left > EPS_BYTES
        rates, n_fill = waterfill_rates(inc, cap,
                                        active.astype(inc.dtype))
        pos = active & (rates > 0)
        dt = jnp.min(jnp.where(
            pos, bytes_left / jnp.where(pos, rates, 1.0), jnp.inf))
        moved = jnp.where(active, rates * dt, 0.0)
        link_bytes = link_bytes + jnp.minimum(moved, bytes_left) @ inc
        bytes_left = jnp.maximum(bytes_left - moved, 0.0)
        newly = active & (bytes_left <= EPS_BYTES)
        done = jnp.where(newly, t + dt, done)
        return bytes_left, t + dt, done, link_bytes, it + 1, fills + n_fill

    zero = jnp.asarray(0, dtype=jnp.int32)
    init = (bytes0, jnp.asarray(0.0, dtype=inc.dtype),
            jnp.zeros(F, dtype=inc.dtype), jnp.zeros(L, dtype=inc.dtype),
            zero, zero)
    bytes_left, t, done, link_bytes, events, fills = lax.while_loop(
        cond, body, init)
    # Parity with the numpy reference's loud failure: a run that exits
    # with unfinished flows (event-guard hit, or a zero-rate stall whose
    # dt=inf poisoned the carry) must not report a silently truncated
    # latency — surface NaN instead, matching simulate_flows' RuntimeError.
    bad = jnp.any(bytes_left > EPS_BYTES) | ~jnp.isfinite(t)
    nan = jnp.asarray(jnp.nan, dtype=inc.dtype)
    return (jnp.where(bad, nan, t), jnp.where(bad, nan, done),
            jnp.where(bad, nan, link_bytes), events, fills)


@functools.lru_cache(maxsize=None)
def _batch_inner():
    """``vmap(waterfill_times)`` over a leading grid axis — unjitted, so
    it doubles as the shard_map target of the sharded sweep fabric
    (DESIGN.md §15)."""
    def one(cap, inc, msg):
        t, done, link_bytes, _, _ = waterfill_times(cap, inc, msg)
        return {"latency": t, "done": done, "link_bytes": link_bytes}

    return jax.vmap(one)


@functools.lru_cache(maxsize=None)
def _batch_fn():
    """``jit(vmap(waterfill_times))`` — one compiled executable per
    (G, F, L) shape signature (cached by jit)."""
    return jax.jit(_batch_inner())


def simulate_pull_batch(caps, incs, msgs,
                        devices: str = "single") -> dict[str, np.ndarray]:
    """Batched flow simulation: ``caps [G, L]``, ``incs [G, F, L]``,
    ``msgs [G, F]`` → dict of numpy float64 arrays (``latency [G]``,
    ``done [G, F]``, ``link_bytes [G, L]``). One compiled call per shape
    signature covers the whole grid; ``devices`` (DESIGN.md §15) shards
    the grid axis across local devices — a sharded grid also runs each
    shard's lockstep ``while_loop`` only as long as its *local* slowest
    point, not the global one."""
    from . import sweep_shard

    G = int(np.shape(caps)[0])
    with x64():
        args = (jnp.asarray(caps, dtype=jnp.float64),
                jnp.asarray(incs, dtype=jnp.float64),
                jnp.asarray(msgs, dtype=jnp.float64))
        if sweep_shard.resolve_devices(devices, G) == "sharded":
            out = sweep_shard.sharded_grid_call(
                _batch_inner(), args, (True, True, True), G)
        else:
            out = _batch_fn()(*args)
        return {k: np.asarray(v) for k, v in out.items()}
