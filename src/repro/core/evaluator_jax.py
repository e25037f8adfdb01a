"""JAX backend for the analytical evaluator — jit + vmap over populations.

Mirrors :meth:`repro.core.evaluator.Evaluator.evaluate_batch` (eqs. 3–12)
op-for-op so the two backends agree to float64 round-off; the numpy
implementation stays the reference and the parity suite
(``tests/test_backend_parity.py``) asserts the contract (DESIGN.md §8).

Structure:
  * every per-(Task, HWConfig) constant — GEMM dims, hop matrices,
    entrance masks, Table-2 scalars — travels in an :class:`EvalConsts`
    dict pytree *argument* rather than a trace-time closure, so one
    compiled executable serves every config with the same shape signature
    (the sweep engine in :mod:`repro.core.sweep` stacks these along a grid
    axis and vmaps over them);
  * :func:`population_fn` = ``jit(vmap(single-candidate))`` — the GA
    fitness path; :func:`grid_fn` adds a second vmap over the grid axis;
  * all entry points run under the :func:`repro.core.x64.x64` scope — cycle
    counts overflow float32 mantissas (same float64 rule as the numpy
    path) and the scope keeps x64 from leaking into the rest of the
    repo's float32 jax code.

Only the modeling toggles (:class:`EvalOptions` fields — redistribution,
async_exec, energy_mode, congestion) are static: they select code paths,
so each combination compiles once per shape signature and is cached in
``population_fn`` / ``grid_fn``. The ``congestion="flow"`` path traces
the max-min waterfilling netsim (:mod:`repro.core.netsim_jax`) inside
the same jit, vmapped over the op axis (DESIGN.md §11).
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp

from ..runtime.spans import span
from .evaluator import EvalOptions
from .netsim_jax import waterfill_times
from .x64 import cumsum_seq, x64

__all__ = [
    "EvalConsts",
    "consts_from_evaluator",
    "population_fn",
    "grid_fn",
    "batch_evaluate",
]

#: dict pytree of per-(Task, HWConfig) constants; see CONST_KEYS.
EvalConsts = Dict[str, Any]

#: Array-valued keys ([n]: per-op, [X,Y]: per-chiplet, [E...]: per-entrance,
#: [2,W]/[XY,W]: each flow site's links, DESIGN.md §11) followed by the 0-d
#: scalar keys. Order is the canonical stacking order used by the sweep
#: engine.
CONST_KEYS = (
    # per-op [n]
    "M", "K", "N", "sync", "w_scale", "epilogue", "chain_valid",
    # per-chiplet [X, Y] (hop matrices + heterogeneous rate arrays;
    # homogeneous configs broadcast the scalar rates, bitwise)
    "hA", "hW", "h_min", "bw_nop_xy", "freq_xy",
    # per-row/cross-row redistribution bottlenecks [X] / [X-1]
    "row_bw", "cross_bw",
    # per-entrance ("bw_ent" is the [E] off-chip share, "bw_nop_ent"
    # the [E] entrance-link NoP rate)
    "row_mask", "col_mask", "ent_mask", "ent_pos", "is3d", "links",
    "bw_ent", "bw_nop_ent",
    # link-level flow network (congestion="flow")
    "flow_cap", "dist_inc", "coll_inc",
    # scalars (0-d)
    "B", "bw_nop_min", "R", "C",
    "e_sram", "e_mem", "e_nop", "e_mac",
)

#: Group tables of a task with uneven grouped GEMMs, present only then
#: (so a task without them traces the same program as before they
#: existed): ``g_idx`` [ng] int32 the grouped ops, ``g_map`` [n] int32
#: 1 + j for grouped op j and 0 elsewhere, ``g_off`` [ng, G+1] their
#: group row offsets.
GROUP_KEYS = ("g_idx", "g_map", "g_off")

#: Per-lane work counts of the flow netsim's two call sites (``dist``:
#: the input-load flows, ``coll``: the collection flows), [n] per
#: candidate in ``congestion="flow"`` mode: event-loop iterations and
#: waterfilling iterations (:func:`repro.core.netsim_jax.waterfill_times`).
#: :func:`_run_x64` takes them out of the outputs, so no record carries
#: them.
LANE_KEYS = ("dist_events", "dist_fills", "coll_events", "coll_fills")


def consts_from_evaluator(ev) -> EvalConsts:
    """Extract the constant bundle from a (numpy) Evaluator instance.

    Returns plain float64/bool numpy arrays — conversion to device arrays
    happens inside the x64 scope at call time.
    """
    hw = ev.hw
    f8 = lambda a: np.asarray(a, dtype=np.float64)
    if ev.opts.congestion == "flow":
        # each site over the links its flows use (Topology.flow_sites)
        flow_cap, dist_inc, coll_inc = ev.top.flow_sites()
    else:
        # Regime mode never reads the flow network; ship 1-element
        # placeholders instead of the [X·Y, L] incidence matrices —
        # consts are stacked per sweep point and moved to device, and
        # XLA's dead-code elimination cannot recover that traffic.
        flow_cap = dist_inc = coll_inc = np.zeros(1)
    groups = {}
    if len(ev.g_idx):
        g_map = np.zeros(len(ev.M), dtype=np.int32)
        g_map[ev.g_idx] = np.arange(1, len(ev.g_idx) + 1)
        groups = {"g_idx": np.asarray(ev.g_idx, dtype=np.int32),
                  "g_map": g_map, "g_off": f8(ev.g_off)}
    return groups | {
        "flow_cap": f8(flow_cap),
        "dist_inc": f8(dist_inc), "coll_inc": f8(coll_inc),
        "M": f8(ev.M), "K": f8(ev.K), "N": f8(ev.N),
        "sync": f8(ev.sync),
        "w_scale": f8(ev.w_scale), "epilogue": f8(ev.epilogue),
        "chain_valid": f8(ev.chain_valid),
        "hA": f8(ev.hA), "hW": f8(ev.hW), "h_min": f8(ev.h_min),
        "row_mask": f8(ev.row_mask), "col_mask": f8(ev.col_mask),
        "ent_mask": f8(ev.ent_mask), "ent_pos": f8(ev.ent_pos),
        "is3d": np.asarray(ev.top.entrance_is_3d, dtype=bool),
        "links": f8(ev.links),
        "bw_nop_xy": f8(ev.bw_nop_xy), "freq_xy": f8(ev.freq_xy),
        "row_bw": f8(ev.row_bw), "cross_bw": f8(ev.cross_bw),
        "bw_ent": f8(ev.bw_ent_e), "bw_nop_ent": f8(ev.bw_nop_ent),
        "B": f8(ev.B), "bw_nop_min": f8(ev.bw_nop_min),
        "R": f8(float(hw.R)), "C": f8(float(hw.C)),
        "e_sram": f8(hw.e_sram_bit * 8.0), "e_mem": f8(hw.e_mem_bit * 8.0),
        "e_nop": f8(hw.e_nop_bit_hop * 8.0), "e_mac": f8(hw.e_mac_cycle),
    }


def _by_op(c: EvalConsts, g, base):
    """Per-op array: row j of ``g`` for grouped op j, ``base`` (broadcast
    to the op axis) for every other op."""
    full = jnp.concatenate([jnp.zeros_like(g[:1]), g])[c["g_map"]]
    grouped = (c["g_map"] > 0).reshape((-1,) + (1,) * (g.ndim - 1))
    return jnp.where(grouped, full, base)


def _group_terms(c: EvalConsts, Px):
    """The grouped-GEMM factors of ``Evaluator._group_terms`` for one
    candidate: ``rows`` [n,X], ``fetch`` [n,E], ``held`` [n], ``tiles``
    [n,X]. Tile counts are integer ceil-divisions, exact on any device."""
    X = Px.shape[1]
    Pg = Px[c["g_idx"]]                                         # [ng,X]
    hi = cumsum_seq(Pg, axis=-1)
    lo = hi - Pg
    off = c["g_off"]
    r = jnp.maximum(jnp.minimum(hi[..., None], off[:, None, 1:])
                    - jnp.maximum(lo[..., None], off[:, None, :-1]), 0.0)
    has = (r > 0).astype(Px.dtype)                              # [ng,X,G]
    t = has.sum(axis=-1)
    fetch = (jnp.einsum("ex,jxg->jeg", c["row_mask"], has) > 0).sum(axis=-1)
    R = c["R"].astype(jnp.int32)
    tiles = ((jnp.rint(r).astype(jnp.int32) + R - 1) // R).sum(axis=-1)
    return {"rows": _by_op(c, t, 1.0),
            "fetch": _by_op(c, fetch.astype(Px.dtype), 1.0),
            "held": _by_op(c, t.sum(axis=-1), float(X)),
            "tiles": _by_op(c, tiles.astype(Px.dtype),
                            jnp.ceil(Px / c["R"]))}


def _eval_single(c: EvalConsts, Px, Py, collectors, redist, *,
                 redistribution: bool, async_exec: bool, energy_mode: str,
                 congestion: str = "regime", smooth: bool = False):
    """One candidate: Px [n,X], Py [n,Y], collectors [n], redist [n].

    Line-for-line port of ``Evaluator.evaluate_batch`` with the population
    axis removed (vmap adds it back). Static python ints n/X/Y come from
    the traced shapes; R/C/bandwidths stay traced so compilations are
    shared across HWConfigs of equal shape.

    ``smooth=True`` replaces the ``ceil(P/unit)`` tile counts — zero
    gradient almost everywhere — with their continuous relaxation
    ``P/unit``, making the whole objective reverse-differentiable for
    the projected-gradient seeding of :mod:`repro.core.cosearch`
    (DESIGN.md §16). Only the ``congestion="regime"`` path is
    differentiable (the flow netsim's waterfilling ``while_loop`` has no
    reverse rule); search/scoring always runs ``smooth=False``.
    """
    n, X = Px.shape
    Y = Py.shape[1]
    # "bw_ent" is the per-entrance [E] off-chip share; per-[n,E] terms
    # divide by it with a plain last-axis broadcast.
    B, bw_ent = c["B"], c["bw_ent"]
    R, C = c["R"], c["C"]
    M, K, N = c["M"], c["K"], c["N"]
    sync = c["sync"]

    redist = redist * c["chain_valid"]
    if not redistribution:
        redist = jnp.zeros_like(redist)
    # redist_in[i] = output of op i-1 was redistributed (A already local).
    redist_in = jnp.concatenate([jnp.zeros_like(redist[:1]), redist[:-1]])
    keepA = 1.0 - redist_in
    redist_out = redist

    # ---------------------------------------------------- data volumes
    chunk = Px[:, :, None] * Py[:, None, :] * B                # [n,X,Y]
    inA = Px * K[:, None] * B                                  # [n,X]
    inW = Py * (K * c["w_scale"])[:, None] * B                 # [n,Y]

    # ----------------------------------------------- phase 1: data load
    A_e = jnp.einsum("ex,nx->ne", c["row_mask"], inA)
    W_e = jnp.einsum("ey,ny->ne", c["col_mask"], inW)
    grouped = _group_terms(c, Px) if "g_idx" in c else None
    if grouped is not None:
        W_e = W_e * grouped["fetch"]

    def inW_xy():
        """Weight bytes per chiplet, [n,1|X,Y]; built at each use, as the
        broadcast was before group tables existed, so that a task without
        them lowers to the same program."""
        w = inW[:, None, :]
        return w if grouped is None else w * grouped["rows"][:, :, None]

    t_off_in = ((keepA[:, None] * A_e + W_e) / bw_ent).max(axis=-1)

    tA_xy = inA[:, :, None] * c["hA"][None]                    # bytes*hops
    tW_xy = inW_xy() * c["hW"][None]
    nop_in_xy = ((keepA[:, None, None] * tA_xy + tW_xy)
                 / c["bw_nop_xy"][None])
    t_nop_in = nop_in_xy.max(axis=(-1, -2))

    flow_mode = congestion == "flow"
    if flow_mode:
        # §11 flow congestion: trace the waterfilling netsim per op
        # (vmapped over the op axis) against the topology's mesh-only
        # flow network — simulated per-chiplet NoP arrival times replace
        # the hop-matrix closed form; off-chip serialization keeps the
        # exact per-entrance term. Routeless chiplets (on their
        # entrance / under a 3D stack) are masked to zero bytes.
        d_routed = (c["dist_inc"].sum(axis=1) > 0).astype(inA.dtype)
        c_routed = (c["coll_inc"].sum(axis=1) > 0).astype(inA.dtype)
        demand = (keepA[:, None, None] * inA[:, :, None]
                  + inW_xy()).reshape(n, X * Y) * d_routed

        def dist_one(b):
            _, done, _, events, fills = waterfill_times(
                c["flow_cap"][0], c["dist_inc"], b)
            return done, events, fills

        def coll_one(b):
            t, _, _, events, fills = waterfill_times(
                c["flow_cap"][1], c["coll_inc"], b)
            return t, events, fills

        dist_done, dist_events, dist_fills = jax.vmap(dist_one)(demand)
        dist_done = dist_done.reshape(n, X, Y)
        t_coll_flow, coll_events, coll_fills = jax.vmap(coll_one)(
            chunk.reshape(n, X * Y) * c_routed)
        t_in = jnp.maximum(t_off_in, dist_done.max(axis=(-1, -2)))
    else:
        t_in = jnp.maximum(t_off_in, t_nop_in)

    # -------------------------------------------------- phase 2: compute
    fill = (2.0 * R + C + K - 2.0)[:, None, None]
    if smooth:
        tiles = (Px / R)[:, :, None] * (Py / C)[:, None, :]
    else:
        row_tiles = jnp.ceil(Px / R) if grouped is None else grouped["tiles"]
        tiles = row_tiles[:, :, None] * jnp.ceil(Py / C)[:, None, :]
    cyc = fill * tiles
    cyc = cyc + c["epilogue"][:, None, None] * Px[:, :, None] \
        * Py[:, None, :] / C
    t_comp_xy = cyc / c["freq_xy"][None]
    t_comp = t_comp_xy.max(axis=(-1, -2))

    # ------------------------------------------- phase 3a: offload path
    out_e = jnp.einsum("exy,nxy->ne", c["ent_mask"], chunk)
    out_at_ent = jnp.einsum("exy,nxy->ne", c["ent_pos"], chunk)
    nonlocal_out = out_e - jnp.where(c["is3d"][None, :], out_at_ent, 0.0)
    links = c["links"][None, :]
    links_safe = jnp.where(links > 0, links, 1.0)
    t_collect = jnp.where(
        links > 0, nonlocal_out / (links_safe * c["bw_nop_ent"][None, :]),
        0.0,
    ).max(axis=-1)
    t_off_out = (out_e / bw_ent).max(axis=-1)
    t_offload = jnp.maximum(t_coll_flow if flow_mode else t_collect,
                            t_off_out)

    # ----------------------------------- phase 3b: redistribution path
    yidx = jnp.arange(Y)[None, :]
    cc = collectors[:, None]
    left_m = (yidx < cc).astype(jnp.float64)
    right_m = (yidx > cc).astype(jnp.float64)
    left_x = jnp.einsum("nxy,ny->nx", chunk, left_m)
    right_x = jnp.einsum("nxy,ny->nx", chunk, right_m)
    t1 = (jnp.maximum(left_x, right_x) / c["row_bw"][None]).max(axis=-1)
    rowbytes = Px * N[:, None] * B                             # [n,X]
    t2 = (rowbytes / c["row_bw"][None]).max(axis=-1)
    cumf = cumsum_seq(Px, axis=-1) / jnp.maximum(M[:, None], 1.0)
    cumf_next = jnp.concatenate([cumf[1:], cumf[-1:]], axis=0)
    if X > 1:
        crossing = jnp.abs(cumf - cumf_next)[:, : X - 1] * M[:, None]
        cross_bytes = crossing * N[:, None] * B
        t3 = (cross_bytes / c["cross_bw"][None]).max(axis=-1)
    else:
        cross_bytes = jnp.zeros_like(cumf[:, :0])
        t3 = jnp.zeros_like(t1)
    t_redist = t1 + t2 + t3

    t_out = jnp.where(redist_out > 0, t_redist, t_offload)

    t_sync = (sync * (Px.max(axis=-1) * 4.0 * B * max(Y - 1, 1))
              / c["bw_nop_min"])

    # ----------------------------------------------------- schedule
    if async_exec:
        fused_xy = (dist_done if flow_mode else nop_in_xy) + t_comp_xy
        t_fused = jnp.maximum(fused_xy.max(axis=(-1, -2)), t_off_in)
        core = jnp.where(sync > 0, t_in + t_comp, t_fused)
    else:
        core = t_in + t_comp
    t_ops = core + t_out + t_sync
    latency = t_ops.sum()

    # ------------------------------------------------------- energy
    w_held = X if grouped is None else grouped["held"]      # rows holding W
    sram_bytes = (Y * inA.sum(axis=-1) + w_held * inW.sum(axis=-1)
                  + chunk.sum(axis=(-1, -2)))
    E_sram = c["e_sram"] * sram_bytes.sum()

    if energy_mode == "paper":
        E_mac = c["e_mac"] * (cyc.max(axis=(-1, -2)) * R * C * X * Y).sum()
    else:
        E_mac = c["e_mac"] * (cyc.sum(axis=(-1, -2)) * R * C).sum()

    mem_bytes = (keepA[:, None] * A_e + W_e
                 + (1.0 - redist_out)[:, None] * out_e).sum()
    E_mem = c["e_mem"] * mem_bytes

    load_bh = (keepA[:, None, None] * tA_xy + tW_xy).sum(axis=(-1, -2))
    collect_bh = (chunk * c["h_min"][None]).sum(axis=(-1, -2))
    red_bh = (
        (left_x + right_x).sum(axis=-1)
        + rowbytes.sum(axis=-1) * max(Y - 1, 1)
        + (cross_bytes.sum(axis=-1) * Y if X > 1 else 0.0)
    )
    nop_bh = load_bh + jnp.where(redist_out > 0, red_bh, collect_bh)
    E_nop = c["e_nop"] * nop_bh.sum()

    energy = E_sram + E_mac + E_mem + E_nop
    out = {
        "latency": latency,
        "energy": energy,
        "edp": energy * latency,
        "t_in": t_in,
        "t_comp": t_comp,
        "t_out": t_out,
        "E_sram": E_sram,
        "E_mac": E_mac,
        "E_mem": E_mem,
        "E_nop": E_nop,
    }
    if flow_mode:
        out.update(dist_events=dist_events, dist_fills=dist_fills,
                   coll_events=coll_events, coll_fills=coll_fills)
    return out


def to_device(consts: EvalConsts) -> EvalConsts:
    """Convert a constant bundle to float64 device arrays once, so repeated
    population calls skip host→device transfer (no-op on device arrays)."""
    with x64():
        return {k: jnp.asarray(v) for k, v in consts.items()}


def _static_key(opts: EvalOptions) -> tuple:
    return (bool(opts.redistribution), bool(opts.async_exec),
            opts.energy_mode, opts.congestion)


@functools.lru_cache(maxsize=None)
def population_fn(redistribution: bool, async_exec: bool, energy_mode: str,
                  congestion: str = "regime"):
    """``jit(vmap(candidate))``: (consts, Px[P,n,X], Py[P,n,Y],
    collectors[P,n], redist[P,n]) → dict of [P]/[P,n] arrays."""
    single = functools.partial(
        _eval_single, redistribution=redistribution,
        async_exec=async_exec, energy_mode=energy_mode,
        congestion=congestion)
    return jax.jit(jax.vmap(single, in_axes=(None, 0, 0, 0, 0)))


@functools.lru_cache(maxsize=None)
def _grid_inner(redistribution: bool, async_exec: bool, energy_mode: str,
                congestion: str = "regime"):
    """Unjitted grid×population function — the shard_map target of the
    sharded sweep fabric (DESIGN.md §15). Cached so the sharded wrapper
    in :mod:`repro.core.sweep_shard` keys its jit cache on a stable
    function identity."""
    single = functools.partial(
        _eval_single, redistribution=redistribution,
        async_exec=async_exec, energy_mode=energy_mode,
        congestion=congestion)
    over_pop = jax.vmap(single, in_axes=(None, 0, 0, 0, 0))
    return jax.vmap(over_pop, in_axes=(0, 0, 0, 0, 0))


@functools.lru_cache(maxsize=None)
def grid_fn(redistribution: bool, async_exec: bool, energy_mode: str,
            congestion: str = "regime"):
    """Grid×population form for the sweep engine: consts stacked on a
    leading grid axis, genomes shaped [G,P,...]; one compiled call per
    shape signature covers the whole grid group."""
    return jax.jit(_grid_inner(redistribution, async_exec, energy_mode,
                               congestion))


def _host_bytes(*trees) -> int:
    """Bytes of the numpy arrays among the leaves: what ``jnp.asarray``
    copies to the device (device arrays move nothing)."""
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(trees)
               if isinstance(a, np.ndarray))


def _run_x64(fn, consts: EvalConsts, Px, Py, collectors, redist
             ) -> tuple[dict[str, np.ndarray], dict[str, np.ndarray]]:
    """Shared call wrapper: float64 conversion inside the x64 scope,
    numpy float64 outputs with the numpy backend's keys/shapes, and
    apart from them the flow mode's per-lane counts (:data:`LANE_KEYS`;
    empty in regime mode)."""
    genomes = (Px, Py, collectors, redist)
    with x64():
        with span("eval.to_device", bytes=_host_bytes(consts, genomes)):
            cj = {k: jnp.asarray(v) for k, v in consts.items()}
            args = [jnp.asarray(a, dtype=jnp.float64) for a in genomes]
        with span("eval.call"):
            out = fn(cj, *args)
        with span("eval.fetch"):
            out = {k: np.asarray(v) for k, v in out.items()}
    lanes = {k: out.pop(k) for k in LANE_KEYS if k in out}
    return out, lanes


def batch_evaluate(consts: EvalConsts, opts: EvalOptions,
                   Px, Py, collectors, redist) -> dict[str, np.ndarray]:
    """Population-batched evaluation (genomes [P,...]) — the GA path."""
    return _run_x64(population_fn(*_static_key(opts)),
                    consts, Px, Py, collectors, redist)[0]


def grid_evaluate(consts_stack: EvalConsts, opts: EvalOptions,
                  Px, Py, collectors, redist,
                  devices: str = "single") -> dict[str, np.ndarray]:
    """Grid-batched evaluation: every array carries a leading grid axis
    (consts [G,...], genomes [G,P,...]); used by :mod:`repro.core.sweep`.

    ``devices`` (DESIGN.md §15) shards the grid axis across local
    devices via :mod:`repro.core.sweep_shard`; outputs are bitwise
    identical to the single-device call.

    In flow mode each netsim call site leaves one ``eval.lanes`` marker
    span: its ``lanes`` (grid x population x ops), the event-loop
    iterations summed over them and their maximum (the lockstep count of
    the vmapped loop), the same two for the waterfilling iterations
    (whose lockstep count is at least ``fills_max``), the ``links`` its
    loops ran over and ``links_used``, the most of them any grid point's
    flows use."""
    G = int(np.shape(Px)[0])
    fn = grid_fn(*_static_key(opts))
    from . import sweep_shard

    if sweep_shard.resolve_devices(devices, G) == "sharded":
        inner = _grid_inner(*_static_key(opts))

        def fn(*args):
            return sweep_shard.sharded_grid_call(
                inner, args, (True,) * 5, G)
    out, lanes = _run_x64(fn, consts_stack, Px, Py, collectors, redist)
    for site in ("dist", "coll"):
        if f"{site}_events" in lanes:
            events = lanes[f"{site}_events"]
            fills = lanes[f"{site}_fills"]
            inc = np.asarray(consts_stack[f"{site}_inc"])       # [G,F,W]
            with span("eval.lanes", site=site, lanes=int(events.size),
                      events_sum=int(events.sum(dtype=np.int64)),
                      events_max=int(events.max(initial=0)),
                      fills_sum=int(fills.sum(dtype=np.int64)),
                      fills_max=int(fills.max(initial=0)),
                      links=int(inc.shape[-1]),
                      links_used=int(inc.any(axis=-2).sum(axis=-1).max())):
                pass
    return out
