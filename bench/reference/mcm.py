"""Plain numpy reference of the MCMComm cost model, kept with the benchmark.

It builds the task graph and the package from a configuration file and
scores a partition with the paper's equations (arXiv:2505.00041 Sec. 4.2-5.3),
under either congestion model: the closed-form regime pick or the max-min
waterfilling flow simulation of the mesh.

It imports nothing of the system under test and takes nothing the system
made: every constant comes from the configuration file or from the
Table 2 values below. It follows the system's own numpy reference
operation for operation, so a correct float64 engine agrees with it to
float64 round-off. Only homogeneous packages (one chiplet class) are
covered, which is what every configuration of the benchmark states.
"""
from __future__ import annotations

import dataclasses

import numpy as np

#: arXiv:2505.00041 Table 2. Bandwidths in bytes/s, energies in J/bit,
#: MAC energy in J/cycle.
TABLE2 = {
    "bw_hbm": 1000e9, "bw_dram": 60e9, "bw_nop": 60e9,
    "e_nop_bit_hop": 1.285e-12, "e_dram_bit": 14.8e-12,
    "e_hbm_bit": 4.11e-12, "e_sram_bit": 0.28e-12, "e_mac_cycle": 4.6e-12,
    "freq_hz": 1.0e9,
}

#: A flow counts as finished below this many bytes.
EPS_BYTES = 1e-6
MAX_EVENTS = 10000


# ------------------------------------------------------------------ graphs
@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    M: int
    K: int
    N: int
    sync: bool = False
    chained: bool = False
    w_scale: float = 1.0
    epilogue: int = 0


def vit_ops(batch=1, depth=12, d=768, heads=12, mlp_ratio=4, tokens=197,
            patch_dim=768):
    """ViT (arXiv:2010.11929) as a GEMM chain; attention heads are a
    grouped GEMM flattened onto M, softmax and layernorm are syncs."""
    m = tokens * batch
    dh = d // heads
    ops = [Op("patch_embed", m, patch_dim, d)]
    for b in range(depth):
        p = f"blk{b}."
        ops += [
            Op(p + "qkv", m, d, 3 * d, sync=True, chained=True),
            Op(p + "scores", tokens * heads * batch, dh, tokens, sync=True,
               w_scale=float(heads * batch), epilogue=5),
            Op(p + "ctx", tokens * heads * batch, tokens, dh,
               w_scale=float(heads * batch)),
            Op(p + "proj", m, d, d),
            Op(p + "fc1", m, d, mlp_ratio * d, sync=True, chained=True,
               epilogue=4),
            Op(p + "fc2", m, mlp_ratio * d, d, chained=True),
        ]
    ops.append(Op("head", batch, d, 1000))
    return ops


def alexnet_ops(batch=1):
    """AlexNet as an im2col GEMM chain: every layer consumes the last."""
    convs = [("conv1", 55 * 55, 11, 3, 96), ("conv2", 27 * 27, 5, 96, 256),
             ("conv3", 13 * 13, 3, 256, 384), ("conv4", 13 * 13, 3, 384, 384),
             ("conv5", 13 * 13, 3, 384, 256)]
    ops = [Op(n, s * batch, ci * k * k, co, chained=i > 0, epilogue=1)
           for i, (n, s, k, ci, co) in enumerate(convs)]
    ops += [Op(n, batch, k, nn, chained=True, epilogue=int(n != "fc8"))
            for n, k, nn in (("fc6", 9216, 4096), ("fc7", 4096, 4096),
                             ("fc8", 4096, 1000))]
    return ops


GRAPHS = {"vit": vit_ops, "alexnet": alexnet_ops}


def graph_ops(workload: dict) -> list[Op]:
    kw = {k: v for k, v in workload.items() if k != "graph"}
    return GRAPHS[workload["graph"]](**kw)


# ---------------------------------------------------------------- topology
def entrances(t: str, X: int, Y: int):
    if t == "A":
        return [(0, 0, "corner")]
    edge = []
    for gx in range(X):
        edge.append((gx, 0, "edge"))
        if Y > 1:
            edge.append((gx, Y - 1, "edge"))
    if t == "B":
        return edge
    if t == "C":
        return [(gx, gy, "3d") for gx in range(X) for gy in range(Y)]
    if t == "D":
        out = list(edge)
        for gx in sorted({(X - 1) // 2, X // 2}):
            for gy in sorted({(Y - 1) // 2, Y // 2}):
                if 0 < gy < Y - 1 or Y <= 2:
                    out.append((gx, gy, "3d"))
        return out
    raise ValueError(f"unknown MCM type {t}")


def mesh_links_at(gx, gy, X, Y, diagonal):
    n = (gx > 0) + (gx < X - 1) + (gy > 0) + (gy < Y - 1)
    if diagonal and ((gx < X - 1 and gy < Y - 1) or (gx > 0 and gy > 0)):
        n += 1
    return n


@dataclasses.dataclass
class Package:
    """One package as the configuration states it, plus its geometry."""

    mcm_type: str
    X: int
    Y: int
    R: int
    C: int
    bw_nop: float
    bw_mem: float
    freq_hz: float
    bytes_per_elem: int
    e_nop_bit_hop: float
    e_mem_bit: float
    e_sram_bit: float
    e_mac_cycle: float
    diagonal_links: bool = False

    def __post_init__(self):
        X, Y = self.X, self.Y
        ents = entrances(self.mcm_type, X, Y)
        self.ents = ents
        gx = np.arange(X)[:, None] * np.ones((1, Y), dtype=int)
        gy = np.ones((X, 1), dtype=int) * np.arange(Y)[None, :]
        dist = np.stack([np.abs(gx - ex) + np.abs(gy - ey)
                         for ex, ey, _ in ents])
        eid = np.argmin(dist, axis=0)
        ex = np.array([e[0] for e in ents])
        ey = np.array([e[1] for e in ents])
        x, y = np.abs(gx - ex[eid]), np.abs(gy - ey[eid])
        Xg, Yg = np.ones((X, Y), int), np.ones((X, Y), int)
        for e in range(len(ents)):
            m = eid == e
            if m.any():
                Xg[m], Yg[m] = x[m].max() + 1, y[m].max() + 1
        self.entrance_id = eid
        E = len(ents)
        self.ent_mask = np.stack([eid == e for e in range(E)])
        self.ent_pos = np.zeros((E, X, Y), dtype=bool)
        for i, (a, b, _) in enumerate(ents):
            self.ent_pos[i, a, b] = True
        self.row_mask = self.ent_mask.any(axis=2)
        self.col_mask = self.ent_mask.any(axis=1)
        self.is3d_e = np.array([k == "3d" for *_, k in ents])
        self.links = np.array([mesh_links_at(a, b, X, Y, self.diagonal_links)
                               for a, b, _ in ents], dtype=np.float64)
        self.bw_ent = np.full(E, self.bw_mem / E)
        self.bw_nop_ent = np.full(E, float(self.bw_nop))
        h_low, h_row, h_col = x + y, Xg + y, Yg + x
        if self.diagonal_links:
            h_row = np.minimum(h_row, Xg - x + np.maximum(x, y))
            h_col = np.minimum(h_col, Yg - y + np.maximum(x, y))
        at_stack = self.is3d_e[eid] & (x == 0) & (y == 0)
        h_low, h_row, h_col = (np.where(at_stack, 0, h)
                               for h in (h_low, h_row, h_col))
        high_bw = self.bw_ent.max() > self.bw_nop
        self.hA = (h_row if high_bw else h_low).astype(np.float64)
        self.hW = (h_col if high_bw else h_low).astype(np.float64)
        self.h_min = h_low.astype(np.float64)
        self._flow = None

    def flow_net(self):
        """``(caps [L], dist_inc [XY, L], coll_inc [XY, L])``: one mesh
        flow per chiplet, memory entrance to chiplet by XY routing (row
        first) and back; memory ports carry no flow."""
        if self._flow is not None:
            return self._flow
        X, Y = self.X, self.Y
        mem = X * Y
        links = []
        for r in range(X):
            for c in range(Y):
                u = r * Y + c
                for rr, cc in ((r + 1, c), (r, c + 1)):
                    if rr < X and cc < Y:
                        v = rr * Y + cc
                        links += [(u, v), (v, u)]
        n_mesh = len(links)
        links += [(mem, c) for c in range(X * Y)]
        links += [(c, mem) for c in range(X * Y)]
        index = {l: i for i, l in enumerate(links)}

        def xy(src, dst):
            (r, c), (r1, c1) = divmod(src, Y), divmod(dst, Y)
            out = []
            while r != r1:
                nr = r + (1 if r1 > r else -1)
                out.append((r * Y + c, nr * Y + c))
                r = nr
            while c != c1:
                nc = c + (1 if c1 > c else -1)
                out.append((r * Y + c, r * Y + nc))
                c = nc
            return out

        attach = [a * Y + b for a, b, _ in self.ents]
        via = [attach[e] for e in self.entrance_id.ravel()]
        dist = np.zeros((X * Y, len(links)))
        coll = np.zeros((X * Y, len(links)))
        for n in range(X * Y):
            for l in xy(via[n], n):
                dist[n, index[l]] = 1.0
            for l in xy(n, via[n]):
                coll[n, index[l]] = 1.0
        caps = np.empty(len(links))
        caps[:n_mesh] = float(self.bw_nop)
        caps[n_mesh:] = float(self.bw_mem) / max(len(attach), 1)
        self._flow = (caps, dist, coll)
        return self._flow


def package(cfg: dict, **override) -> Package:
    p = dict(cfg["package"])
    p.pop("memory", None)
    p.update(override)
    return Package(**p)


# ----------------------------------------------------------------- netsim
def waterfill_rates(inc, cap, active):
    F, L = inc.shape
    residual = cap.astype(np.float64).copy()
    unfixed = active.astype(bool).copy()
    rates = np.zeros(F)
    for _ in range(L + 1):
        users = unfixed.astype(np.float64) @ inc
        live = users > 0
        if not live.any():
            break
        share = np.where(live, residual / np.where(live, users, 1.0), np.inf)
        l = int(np.argmin(share))
        s = share[l]
        newly = unfixed & (inc[:, l] > 0)
        rates[newly] = s
        residual = np.maximum(residual - (newly.astype(np.float64) @ inc) * s,
                              0.0)
        unfixed &= ~newly
    return rates


def simulate_flows(inc, cap, message_bytes):
    """Event-driven max-min fair simulation of concurrent flows; returns
    ``(latency, done [F])``."""
    left = np.asarray(message_bytes, dtype=np.float64).copy()
    t = 0.0
    done = np.zeros(inc.shape[0])
    for _ in range(MAX_EVENTS):
        active = left > EPS_BYTES
        if not active.any():
            return t, done
        rates = waterfill_rates(inc, cap, active)
        pos = active & (rates > 0)
        if not pos.any():
            raise RuntimeError("flow simulation stalled")
        dt = float(np.min(np.where(pos, left / np.where(pos, rates, 1.0),
                                   np.inf)))
        left = np.maximum(left - np.where(active, rates * dt, 0.0), 0.0)
        done = np.where(active & (left <= EPS_BYTES), t + dt, done)
        t += dt
    raise RuntimeError("flow simulation did not converge")


# -------------------------------------------------------------- evaluator
class Reference:
    """Scores partitions of one task graph on one package."""

    def __init__(self, ops: list[Op], pk: Package, options: dict):
        self.ops, self.pk = ops, pk
        self.redistribution = bool(options["redistribution"])
        self.async_exec = bool(options["async_exec"])
        self.energy_mode = options.get("energy_mode", "paper")
        self.flow = options.get("congestion", "regime") == "flow"
        f = lambda a: np.array([getattr(o, a) for o in ops], dtype=np.float64)
        self.M, self.K, self.N = f("M"), f("K"), f("N")
        self.w_scale, self.epi = f("w_scale"), f("epilogue")
        self.sync = np.array([o.sync for o in ops])
        self.chain_valid = np.array(
            [bool(ops[i + 1].chained) for i in range(len(ops) - 1)] + [False])

    def check_partition(self, Px, Py, collectors) -> str | None:
        """Why a genome is not a partition of this graph, or None."""
        n, X, Y = len(self.ops), self.pk.X, self.pk.Y
        if np.shape(Px) != (n, X) or np.shape(Py) != (n, Y) \
                or np.shape(collectors) != (n,):
            return "wrong shape"
        if (np.asarray(Px) < 0).any() or (np.asarray(Py) < 0).any():
            return "negative share"
        if not (np.sum(Px, axis=1) == self.M).all():
            return "row shares do not sum to M"
        if not (np.sum(Py, axis=1) == self.N).all():
            return "column shares do not sum to N"
        c = np.asarray(collectors)
        if ((c < 0) | (c >= Y)).any():
            return "collector out of range"
        return None

    def redist_of(self, mask=None):
        """The redistribution genes a point means: ``None`` is every
        chained pair where the options allow it."""
        if not self.redistribution:
            return np.zeros(len(self.ops))
        if mask is None:
            return self.chain_valid.astype(np.float64)
        return (np.asarray(mask, dtype=bool) & self.chain_valid
                ).astype(np.float64)

    def evaluate(self, Px, Py, collectors, redist) -> dict:
        """One genome; ``redist`` from :meth:`redist_of`."""
        pk = self.pk
        X, Y, R, C = pk.X, pk.Y, float(pk.R), float(pk.C)
        B = float(pk.bytes_per_elem)
        Px = np.asarray(Px, dtype=np.float64)
        Py = np.asarray(Py, dtype=np.float64)
        col = np.asarray(collectors, dtype=np.int64)
        M, K, N = self.M, self.K, self.N
        rd_out = np.asarray(redist, dtype=np.float64) * self.chain_valid
        keepA = 1.0 - np.concatenate([[0.0], rd_out[:-1]])
        bw_ent = pk.bw_ent[None]
        freq = pk.freq_hz
        row_bw = np.full(X, float(pk.bw_nop))
        cross_bw = np.minimum(row_bw[:-1], row_bw[1:])

        chunk = Px[:, :, None] * Py[:, None, :] * B              # [n,X,Y]
        inA = Px * K[:, None] * B
        inW = Py * (K * self.w_scale)[:, None] * B
        A_e = np.einsum("ex,nx->ne", pk.row_mask, inA)
        W_e = np.einsum("ey,ny->ne", pk.col_mask, inW)
        t_off_in = ((keepA[:, None] * A_e + W_e) / bw_ent).max(axis=-1)
        tA = inA[:, :, None] * pk.hA[None]
        tW = inW[:, None, :] * pk.hW[None]
        if self.flow:
            demand = keepA[:, None, None] * inA[:, :, None] + inW[:, None, :]
            dist_done, t_coll = self._flow_times(demand, chunk)
            t_in = np.maximum(t_off_in, dist_done.max(axis=(-1, -2)))
        else:
            nop_in = (keepA[:, None, None] * tA + tW) / pk.bw_nop
            t_in = np.maximum(t_off_in, nop_in.max(axis=(-1, -2)))

        fill = (2.0 * R + C + K - 2.0)[:, None, None]
        tiles = np.ceil(Px / R)[:, :, None] * np.ceil(Py / C)[:, None, :]
        cyc = fill * tiles + (self.epi[:, None, None] * Px[:, :, None]
                              * Py[:, None, :] / C)
        t_comp_xy = cyc / freq
        t_comp = t_comp_xy.max(axis=(-1, -2))

        out_e = np.einsum("exy,nxy->ne", pk.ent_mask, chunk)
        t_off_out = (out_e / bw_ent).max(axis=-1)
        if self.flow:
            t_offload = np.maximum(t_coll, t_off_out)
        else:
            at_ent = np.einsum("exy,nxy->ne", pk.ent_pos, chunk)
            nonlocal_out = out_e - np.where(pk.is3d_e[None], at_ent, 0.0)
            with np.errstate(divide="ignore", invalid="ignore"):
                t_collect = np.where(
                    pk.links[None] > 0,
                    nonlocal_out / (pk.links[None] * pk.bw_nop_ent[None]),
                    0.0).max(axis=-1)
            t_offload = np.maximum(t_collect, t_off_out)

        yidx = np.arange(Y)[None, :]
        left_x = np.einsum("nxy,ny->nx", chunk, (yidx < col[:, None]) * 1.0)
        right_x = np.einsum("nxy,ny->nx", chunk, (yidx > col[:, None]) * 1.0)
        t1 = (np.maximum(left_x, right_x) / row_bw[None]).max(axis=-1)
        rowbytes = Px * N[:, None] * B
        t2 = (rowbytes / row_bw[None]).max(axis=-1)
        cumf = np.cumsum(Px, axis=-1) / np.maximum(M[:, None], 1.0)
        cumf_next = np.concatenate([cumf[1:], cumf[-1:]], axis=0)
        if X > 1:
            crossing = np.abs(cumf - cumf_next)[:, : X - 1] * M[:, None]
            cross_bytes = crossing * N[:, None] * B
            t3 = (cross_bytes / cross_bw[None]).max(axis=-1)
        else:
            cross_bytes = np.zeros((len(M), 0))
            t3 = np.zeros_like(t1)
        t_out = np.where(rd_out > 0, t1 + t2 + t3, t_offload)
        t_sync = self.sync * (Px.max(axis=-1) * 4.0 * B * max(Y - 1, 1)) \
            / float(pk.bw_nop)

        if self.async_exec:
            if self.flow:
                fused = np.maximum((dist_done + t_comp_xy).max(axis=(-1, -2)),
                                   t_off_in)
            else:
                fused = np.maximum((nop_in + t_comp_xy).max(axis=(-1, -2)),
                                   t_off_in)
            core = np.where(self.sync, t_in + t_comp, fused)
        else:
            core = t_in + t_comp
        latency = (core + t_out + t_sync).sum()

        sram = Y * inA.sum(axis=-1) + X * inW.sum(axis=-1) \
            + chunk.sum(axis=(-1, -2))
        E_sram = pk.e_sram_bit * 8.0 * sram.sum()
        if self.energy_mode == "paper":
            E_mac = pk.e_mac_cycle * (cyc.max(axis=(-1, -2)) * R * C
                                      * X * Y).sum()
        else:
            E_mac = pk.e_mac_cycle * (cyc.sum(axis=(-1, -2)) * R * C).sum()
        mem = (keepA[:, None] * A_e + W_e
               + (1.0 - rd_out)[:, None] * out_e).sum()
        E_mem = pk.e_mem_bit * 8.0 * mem
        load = (keepA[:, None, None] * tA + tW).sum(axis=(-1, -2))
        collect = (chunk * pk.h_min[None]).sum(axis=(-1, -2))
        red = ((left_x + right_x).sum(axis=-1)
               + rowbytes.sum(axis=-1) * max(Y - 1, 1)
               + (cross_bytes.sum(axis=-1) * Y if X > 1 else 0.0))
        E_nop = pk.e_nop_bit_hop * 8.0 * (
            load + np.where(rd_out > 0, red, collect)).sum()
        energy = E_sram + E_mac + E_mem + E_nop
        return {"latency": latency, "energy": energy,
                "edp": energy * latency, "t_in": t_in, "t_comp": t_comp,
                "t_out": t_out, "E_sram": E_sram, "E_mac": E_mac,
                "E_mem": E_mem, "E_nop": E_nop}

    def _flow_times(self, demand, chunk):
        caps, dinc, cinc = self.pk.flow_net()
        n, X, Y = demand.shape
        demand = demand * (dinc.sum(axis=1) > 0).reshape(X, Y)
        chunk = chunk * (cinc.sum(axis=1) > 0).reshape(X, Y)
        done = np.zeros((n, X, Y))
        t_coll = np.zeros(n)
        for i in range(n):
            done[i] = simulate_flows(dinc, caps, demand[i].ravel())[1] \
                .reshape(X, Y)
            t_coll[i] = simulate_flows(cinc, caps, chunk[i].ravel())[0]
        return done, t_coll
