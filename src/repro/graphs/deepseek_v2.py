"""DeepSeek-V2 (arXiv:2405.04434) prefill as a GEMM sequence.

Every layer starts with multi-head latent attention (MLA): one down-
projection of the normed hidden state to the query and key/value latents
and the shared rotary key, the two up-projections, per-head scores and
context as grouped GEMMs under ViT's convention (heads flattened onto M,
``weight_bytes_scale`` = heads x batch, softmax a sync and an epilogue),
and the output projection. The first ``layers_dense`` layers end in a
dense SwiGLU MLP; the rest in a mixture of experts: the router, the
shared experts as one SwiGLU of ``n_shared_experts`` x ``moe_d_ff``, and
the routed experts as two uneven grouped GEMMs whose rows are the
token-expert pairs sorted by expert, ``group_sizes`` = the tokens each
expert received (``expert_tokens``, one list per MoE layer). A final
``lm_head`` scores the last position of each sequence.

Two ops are ``chained`` only where the second op's whole input is the
first op's whole output, row for row. Departures from the published
model, each a modelling choice of this graph:

* causal masking is not modelled: ``scores`` and ``ctx`` count the full
  tokens x tokens square;
* the query (q_a) and key/value (kv_a) down-projections read the same
  normed input and are fused into one GEMM, ``qkv_a``;
* expert dispatch and combine go through memory: ``experts_up`` is not
  chained to anything before it, and nothing after ``experts_down`` is
  chained to it;
* the SwiGLU activation (SiLU and the gate product) is an epilogue of
  each up-projection, 4 operations per element as ViT's GELU.
"""
from __future__ import annotations

from typing import Sequence

from ..configs.deepseek_v2_236b import config as _published
from ..core.workload import GemmOp, Task

_P = _published()


def deepseek_v2_task(batch: int = 1, *, tokens: int = 4096,
                     layers_dense: int = _P.first_dense_layers,
                     layers_moe: int = _P.n_layers - _P.first_dense_layers,
                     expert_tokens: Sequence[Sequence[int]] | None = None,
                     d_model: int = _P.d_model, d_ff: int = _P.d_ff,
                     n_heads: int = _P.n_heads,
                     q_lora_rank: int = _P.q_lora_rank,
                     kv_lora_rank: int = _P.kv_lora_rank,
                     qk_nope_dim: int = _P.qk_nope_dim,
                     qk_rope_dim: int = _P.qk_rope_dim,
                     v_head_dim: int = _P.v_head_dim,
                     n_experts: int = _P.n_experts,
                     n_shared_experts: int = _P.n_shared_experts,
                     moe_top_k: int = _P.moe_top_k,
                     moe_d_ff: int = _P.moe_d_ff,
                     vocab_size: int = _P.vocab_size) -> Task:
    """``tokens`` per sequence x ``batch`` sequences in one prefill chunk;
    ``expert_tokens[l]`` the tokens routed to each of the ``n_experts``
    experts of MoE layer l, summing to ``moe_top_k`` x tokens x batch
    (``None``: uniform routing). Depth and width defaults are the
    published ones (:func:`repro.configs.deepseek_v2_236b.config`)."""
    T = tokens * batch
    if expert_tokens is None:
        base, rem = divmod(moe_top_k * T, n_experts)
        expert_tokens = [[base + (e < rem) for e in range(n_experts)]
                         ] * layers_moe
    if len(expert_tokens) != layers_moe:
        raise ValueError(f"expert_tokens has {len(expert_tokens)} layers, "
                         f"layers_moe is {layers_moe}")
    qk = qk_nope_dim + qk_rope_dim
    ops: list[GemmOp] = []
    for layer in range(layers_dense + layers_moe):
        p = f"L{layer}."
        after_dense = bool(ops) and ops[-1].name.endswith("mlp_down")
        ops += [
            GemmOp(p + "qkv_a", M=T, K=d_model,
                   N=q_lora_rank + kv_lora_rank + qk_rope_dim, sync=True,
                   chained=after_dense),
            GemmOp(p + "q_b", M=T, K=q_lora_rank, N=n_heads * qk),
            GemmOp(p + "kv_b", M=T, K=kv_lora_rank,
                   N=n_heads * (qk_nope_dim + v_head_dim)),
            GemmOp(p + "scores", M=n_heads * T, K=qk, N=tokens,
                   n_groups=n_heads, sync=True, epilogue_flops_per_elem=5,
                   weight_bytes_scale=float(n_heads * batch)),
            GemmOp(p + "ctx", M=n_heads * T, K=tokens, N=v_head_dim,
                   n_groups=n_heads,
                   weight_bytes_scale=float(n_heads * batch)),
            GemmOp(p + "o", M=T, K=n_heads * v_head_dim, N=d_model),
        ]
        if layer < layers_dense:
            ops += [
                GemmOp(p + "mlp_up", M=T, K=d_model, N=2 * d_ff,
                       chained=True, sync=True, epilogue_flops_per_elem=4),
                GemmOp(p + "mlp_down", M=T, K=d_ff, N=d_model,
                       chained=True),
            ]
            continue
        counts = tuple(expert_tokens[layer - layers_dense])
        if len(counts) != n_experts:
            raise ValueError(f"{p}: {len(counts)} expert counts for "
                             f"{n_experts} experts")
        shared = n_shared_experts * moe_d_ff
        ops += [
            GemmOp(p + "router", M=T, K=d_model, N=n_experts, chained=True,
                   sync=True, epilogue_flops_per_elem=5),
            GemmOp(p + "shared_up", M=T, K=d_model, N=2 * shared,
                   epilogue_flops_per_elem=4),
            GemmOp(p + "shared_down", M=T, K=shared, N=d_model,
                   chained=True),
            GemmOp(p + "experts_up", M=moe_top_k * T, K=d_model,
                   N=2 * moe_d_ff, n_groups=n_experts, group_sizes=counts,
                   epilogue_flops_per_elem=4),
            GemmOp(p + "experts_down", M=moe_top_k * T, K=moe_d_ff,
                   N=d_model, chained=True, n_groups=n_experts,
                   group_sizes=counts),
        ]
    ops.append(GemmOp("lm_head", M=batch, K=d_model, N=vocab_size))
    return Task(f"deepseek_v2_b{batch}_t{tokens}_"
                f"{layers_dense}d{layers_moe}m", ops)
