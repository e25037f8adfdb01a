"""Plain numpy reference for DeepSeek-V2 on an MCM package: the graph of
its prefill (arXiv:2405.04434) and the paper's cost model with the term
for uneven grouped GEMMs that its routed experts need.

The graph, the widths and the modelling departures are the ones the
configuration file states: multi-head latent attention with q_a and kv_a
fused into one GEMM, per-head scores and context flattened onto M with
their "weights" scaled by heads x batch, no causal mask, a dense SwiGLU
layer, then MoE layers of a router, the shared experts and the routed
experts, whose dispatch and combine go through memory. The widths below
are the published ``config.json``'s.

An op with ``groups`` (tokens per expert, rows in expert order) is scored
with the grouped term: chiplet row x holds rows [S_x, S_x + Px[x]), S
the exclusive cumulative sum of Px; r[x, g] of them belong to expert g
and t_x experts have any. Row x then receives Py[y]·K·B·t_x weight bytes
at chiplet (x, y) (NoP load, flow demand, SRAM), each entrance fetches
every expert that a row it serves needs once per column stripe, and the
chiplet computes Σ_g ceil(r[x, g] / R) row tiles. Everything else is
``bench.reference.mcm``'s, whose package, netsim and checks are reused.
It imports nothing of the system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference import mcm

#: DeepSeek-V2's published widths (config.json of deepseek-ai/DeepSeek-V2).
PUBLISHED = {"hidden_size": 5120, "intermediate_size": 12288,
             "num_attention_heads": 128, "q_lora_rank": 1536,
             "kv_lora_rank": 512, "qk_nope_head_dim": 128,
             "qk_rope_head_dim": 64, "v_head_dim": 128,
             "n_routed_experts": 160, "n_shared_experts": 2,
             "num_experts_per_tok": 6, "moe_intermediate_size": 1536,
             "vocab_size": 102400}

package = mcm.package


@dataclasses.dataclass(frozen=True)
class Op(mcm.Op):
    groups: tuple = ()


def dsv2_ops(batch=1, tokens=4096, layers_dense=1, layers_moe=4,
             expert_tokens=()):
    """The prefill chunk of ``tokens`` x ``batch`` tokens through
    ``layers_dense`` dense and ``layers_moe`` MoE layers, then the head."""
    w = PUBLISHED
    d, H = w["hidden_size"], w["num_attention_heads"]
    nope, rope, vd = (w["qk_nope_head_dim"], w["qk_rope_head_dim"],
                      w["v_head_dim"])
    T = tokens * batch
    ops = []
    for layer in range(layers_dense + layers_moe):
        p = f"L{layer}."
        ops += [
            Op(p + "qkv_a", T, d, w["q_lora_rank"] + w["kv_lora_rank"] + rope,
               sync=True, chained=0 < layer <= layers_dense),
            Op(p + "q_b", T, w["q_lora_rank"], H * (nope + rope)),
            Op(p + "kv_b", T, w["kv_lora_rank"], H * (nope + vd)),
            Op(p + "scores", H * T, nope + rope, tokens, sync=True,
               w_scale=float(H * batch), epilogue=5),
            Op(p + "ctx", H * T, tokens, vd, w_scale=float(H * batch)),
            Op(p + "o", T, H * vd, d),
        ]
        if layer < layers_dense:
            f = w["intermediate_size"]
            ops += [Op(p + "mlp_up", T, d, 2 * f, sync=True, chained=True,
                       epilogue=4),
                    Op(p + "mlp_down", T, f, d, chained=True)]
            continue
        f = w["moe_intermediate_size"]
        shared = w["n_shared_experts"] * f
        groups = tuple(int(c) for c in expert_tokens[layer - layers_dense])
        assert len(groups) == w["n_routed_experts"]
        m = w["num_experts_per_tok"] * T
        ops += [
            Op(p + "router", T, d, w["n_routed_experts"], sync=True,
               chained=True, epilogue=5),
            Op(p + "shared_up", T, d, 2 * shared, epilogue=4),
            Op(p + "shared_down", T, shared, d, chained=True),
            Op(p + "experts_up", m, d, 2 * f, epilogue=4, groups=groups),
            Op(p + "experts_down", m, f, d, chained=True, groups=groups),
        ]
    ops.append(Op("lm_head", batch, d, w["vocab_size"]))
    return ops


def graph_ops(workload: dict) -> list[Op]:
    assert workload["graph"] == "deepseek_v2", workload["graph"]
    kw = {k: v for k, v in workload.items() if k != "graph"}
    return dsv2_ops(**kw)


def grouped_rows(Px_x, groups) -> np.ndarray:
    """r[x, g]: rows of group g on chiplet row x, by a loop over both."""
    bounds = np.concatenate([[0], np.cumsum(groups)])
    r = np.zeros((len(Px_x), len(groups)))
    start = 0.0
    for x, share in enumerate(Px_x):
        end = start + share
        for g in range(len(groups)):
            r[x, g] = max(0.0, min(end, bounds[g + 1]) - max(start, bounds[g]))
        start = end
    return r


class Reference(mcm.Reference):
    """``mcm.Reference`` with the grouped term for ops that have groups."""

    def grouped(self, Px):
        """Per op: weight rows [n,X] (t_x; 1 for a plain op), entrance
        fetches [n,E] (experts the rows it serves need; 1 for a plain op),
        rows holding weights [n] (Σ_x t_x; X) and row tiles [n,X]."""
        pk = self.pk
        n, X, E = len(self.ops), pk.X, len(pk.ents)
        rows, fetch = np.ones((n, X)), np.ones((n, E))
        held = np.full(n, float(X))
        tiles = np.ceil(Px / float(pk.R))
        for i, op in enumerate(self.ops):
            if not op.groups:
                continue
            need = grouped_rows(Px[i], op.groups) > 0
            rows[i] = need.sum(axis=1)
            held[i] = need.sum()
            fetch[i] = [need[pk.row_mask[e]].any(axis=0).sum()
                        for e in range(E)]
            tiles[i] = np.ceil(grouped_rows(Px[i], op.groups)
                               / float(pk.R)).sum(axis=1)
        return rows, fetch, held, tiles

    def evaluate(self, Px, Py, collectors, redist) -> dict:
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
        row_bw = np.full(X, float(pk.bw_nop))
        cross_bw = np.minimum(row_bw[:-1], row_bw[1:])
        w_rows, w_fetch, w_held, row_tiles = self.grouped(Px)

        chunk = Px[:, :, None] * Py[:, None, :] * B              # [n,X,Y]
        inA = Px * K[:, None] * B
        inW = Py * (K * self.w_scale)[:, None] * B
        inW_xy = inW[:, None, :] * w_rows[:, :, None]            # [n,X,Y]
        A_e = np.einsum("ex,nx->ne", pk.row_mask, inA)
        W_e = np.einsum("ey,ny->ne", pk.col_mask, inW) * w_fetch
        t_off_in = ((keepA[:, None] * A_e + W_e) / bw_ent).max(axis=-1)
        tA = inA[:, :, None] * pk.hA[None]
        tW = inW_xy * pk.hW[None]
        if self.flow:
            demand = keepA[:, None, None] * inA[:, :, None] + inW_xy
            dist_done, t_coll = self._flow_times(demand, chunk)
            t_in = np.maximum(t_off_in, dist_done.max(axis=(-1, -2)))
        else:
            nop_in = (keepA[:, None, None] * tA + tW) / pk.bw_nop
            t_in = np.maximum(t_off_in, nop_in.max(axis=(-1, -2)))

        fill = (2.0 * R + C + K - 2.0)[:, None, None]
        tiles = row_tiles[:, :, None] * np.ceil(Py / C)[:, None, :]
        cyc = fill * tiles + (self.epi[:, None, None] * Px[:, :, None]
                              * Py[:, None, :] / C)
        t_comp_xy = cyc / pk.freq_hz
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
            arrive = dist_done if self.flow else nop_in
            fused = np.maximum((arrive + t_comp_xy).max(axis=(-1, -2)),
                               t_off_in)
            core = np.where(self.sync, t_in + t_comp, fused)
        else:
            core = t_in + t_comp
        latency = (core + t_out + t_sync).sum()

        sram = Y * inA.sum(axis=-1) + w_held * inW.sum(axis=-1) \
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
