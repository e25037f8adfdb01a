"""Device-sharded sweep fabric — ``shard_map`` over the grid axis
(DESIGN.md §15).

Every batched engine behind :mod:`repro.core.sweep` evaluates a shape
group as ONE compiled call with a leading *grid* (or *island*) axis:
``evaluator_jax.grid_evaluate`` (``jit(vmap(vmap))``),
``ga_jax.solve_islands`` (``jit(vmap(scan))``),
``netsim_jax.simulate_pull_batch`` and
``pipelining_jax.schedule_batch`` (``jit(vmap(...))``), and the MIQP
lattice scorer's chunked ``grid_evaluate`` calls. Those calls all run on
one device; this module shards that leading axis across every local
device instead:

  * :func:`resolve_devices` — the uniform
    ``devices="single"|"sharded"|"auto"`` knob carried by
    ``EvalOptions``/``GAConfig``/``MIQPConfig``/``PipelineConfig`` (and
    overridable per sweep call / per ``OptServer``). ``"auto"`` picks
    ``"sharded"`` iff more than one device exists and the group has ≥ 2
    points; an explicit ``"sharded"`` always goes through ``shard_map``,
    even on a 1-device mesh, so single-device hosts exercise the exact
    code path multi-device hosts run.
  * :func:`grid_mesh` — the mesh, from
    :func:`repro.launch.mesh.make_debug_mesh` over all local devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=N`` carves a
    CPU host into N devices; ``benchmarks/common.py`` exposes it as the
    ``--devices`` flag).
  * :func:`sharded_grid_call` — pad the grid axis to a multiple of the
    device count (tail points replicate row 0 — *valid* data, so
    ``lax.while_loop``/``scan`` engines terminate on the padding —
    and are sliced off after the call), then run the engine's unjitted
    vmapped inner function under ``jit(shard_map(...))`` with batched
    arguments sharded over dim 0 and the rest replicated.

Exactness (the §9 contract, extended): per-point math inside every
engine is lane-independent — no cross-point reduction, no batch-size-
dependent tie-break — so a point's record is **bitwise identical solo,
batched, or sharded**. The sweep-cache fingerprints therefore normalize
the ``devices`` field away (:func:`repro.core.sweep._strip_devices`):
records are device-count-independent and one cache serves all three
modes. ``tests/test_sweep_shard.py`` pins the contract;
``benchmarks/perf_iterations.py --cell sweep_shard`` gates it bitwise
in CI.

Performance note: on real multi-device hardware the win is ~linear in
device count for the scan/while_loop-bound engines (GA evolution, flow
netsim) whose single-device form cannot use intra-op parallelism. On a
CPU host carved into virtual devices the shards still share the same
physical cores, so forced-host speedups are bounded by the *physical*
core count (the ``sweep_shard`` artifact records both).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from .evaluator import DEVICE_MODES

__all__ = [
    "DEVICE_MODES",
    "device_count",
    "resolve_devices",
    "grid_mesh",
    "sharded_grid_call",
]


def device_count() -> int:
    return len(jax.devices())


def resolve_devices(devices: str | None, n_points: int) -> str:
    """Resolve the ``devices`` knob to a concrete execution mode.

    ``None`` means "auto". ``"auto"`` → ``"sharded"`` iff more than one
    device exists and the group carries ≥ 2 points (sharding a single
    point buys nothing); the choice is correctness-neutral — solo ==
    batched == sharded bitwise — so auto-resolution never splits the
    result cache. An explicit ``"sharded"`` is honored even on one
    device (a 1-device mesh), so the shard_map path is testable
    anywhere."""
    if devices is None:
        devices = "auto"
    if devices not in DEVICE_MODES:
        raise ValueError(f"unknown devices mode {devices!r}; "
                         f"one of {DEVICE_MODES}")
    if devices == "auto":
        return ("sharded" if device_count() > 1 and n_points >= 2
                else "single")
    return devices


@functools.lru_cache(maxsize=None)
def grid_mesh():
    """The sweep fabric's mesh: a debug mesh over ALL local devices
    (cached — mesh identity keys the compiled shard_map wrappers). The
    grid axis is sharded over the product of every mesh axis, so the
    mesh shape (2-D/3-D, :func:`repro.launch.mesh.make_debug_mesh`)
    only affects axis naming, not the sharding."""
    from ..launch.mesh import make_debug_mesh

    return make_debug_mesh()


def _pad0(tree, pad: int):
    """Pad every leaf's leading axis with ``pad`` copies of row 0.
    Replicated *valid* rows — never zeros — so iterative engines
    (waterfilling ``while_loop``, GA ``scan``) behave on the tail
    exactly like they do on a real point."""
    return jax.tree_util.tree_map(
        lambda a: jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])]), tree)


@functools.lru_cache(maxsize=None)
def _sharded_fn(inner, mesh, batched: tuple):
    """``jit(shard_map(inner))`` cached by (inner fn, mesh, batched
    mask) — engines pass lru-cached inner functions, so the jit cache
    never grows per call. ``batched[i]`` shards positional arg ``i``'s
    leading axis over the whole mesh; False replicates (hyperparams,
    shared RNG keys). ``check_vma=False``: per-shard computation is
    independent, there is no replication to infer across lanes."""
    axes = PartitionSpec(tuple(mesh.axis_names))
    in_specs = tuple(axes if b else PartitionSpec() for b in batched)
    return jax.jit(jax.shard_map(inner, mesh=mesh, in_specs=in_specs,
                                 out_specs=axes, check_vma=False))


def sharded_grid_call(inner, args: tuple, batched: tuple, n_points: int,
                      mesh=None):
    """Run ``inner(*args)`` with batched args sharded over the mesh.

    ``inner`` must be the engine's *unjitted* vmapped function (shapes
    [G, ...] on batched args); callers invoke this inside their own
    :func:`repro.core.x64.x64` scope — padding concatenates in
    jnp and must not downcast float64. Pads the grid axis to a multiple
    of the device count, dispatches one compiled shard_map call, slices
    outputs back to ``n_points``."""
    mesh = mesh if mesh is not None else grid_mesh()
    pad = (-n_points) % mesh.size
    if pad:
        args = tuple(_pad0(a, pad) if b else a
                     for a, b in zip(args, batched))
    out = _sharded_fn(inner, mesh, tuple(batched))(*args)
    if pad:
        out = jax.tree_util.tree_map(lambda x: x[:n_points], out)
    return out
