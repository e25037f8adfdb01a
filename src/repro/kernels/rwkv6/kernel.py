"""Pallas TPU kernel for the RWKV-6 chunked WKV recurrence.

Grid (B·H, n_chunks), chunks innermost; the (K, K) WKV state is VMEM
scratch carried across chunk steps. Unlike SSD, the decay here is
*per-channel*, so the intra-chunk pairwise term needs per-channel decay
alignment; the kernel keeps chunks small (Lc ≤ 64) and computes the
(Lc, Lc) interaction with a static loop over the chunk's rows feeding
the MXU (row i's query against all j ≤ i−1 keys decayed relative to
row i), which avoids any (Lc, Lc, K) VMEM tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._common import cumsum_rows


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref, *,
                Lc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)     # (Lc, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)   # (Lc, K) log decay ≤ 0
    u = u_ref[0].astype(jnp.float32)     # (1, K) bonus

    cum = cumsum_rows(lw)                              # (Lc, K)
    cum_im1 = cum - lw                                 # cum_{i-1}
    # intra-chunk pairwise term:
    #   A[i, j] = Σ_c r_i[c]·exp(cum_{i-1}[c] − cum_j[c])·k_j[c],  j < i
    # one row at a time, with exponents normalized per row i so every
    # exp argument of a kept key stays ≤ 0 (cum decreases in i). Rows
    # are static slices: Mosaic has no dynamic_slice lowering.
    rows = jax.lax.broadcasted_iota(jnp.int32, (Lc, 1), 0)
    y_intra = jnp.zeros((Lc, v.shape[-1]), jnp.float32)
    for i in range(1, Lc):                             # row 0: no j < 0
        # keys decayed relative to row i: exp(cum_{i-1} − cum_j) ≤ 1 ∀ j<i
        kd = jnp.where(rows < i, k * jnp.exp(cum_im1[i:i + 1] - cum), 0.0)
        a_i = jax.lax.dot_general(
            r[i:i + 1], kd, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (1, Lc)
        y_i = jnp.dot(a_i, v, preferred_element_type=jnp.float32)
        y_intra += jnp.where(rows == i, y_i, 0.0)
    # bonus diagonal
    diag = jnp.sum(r * u * k, axis=-1, keepdims=True)  # (Lc, 1)
    y_intra += diag * v
    # inter-chunk: y_i += (r_i ∘ exp(cum_{i-1})) @ S   (S: (K, V))
    y_inter = jnp.dot(r * jnp.exp(cum_im1), s_ref[...],
                      preferred_element_type=jnp.float32)
    # state: S' = D(exp(cum_L))·S + Σ_j (k_j ∘ exp(cum_L − cum_j)) ⊗ v_j
    decay_end = jnp.exp(cum[-1:] - cum)                # (Lc, K)
    s_ref[...] = (s_ref[...] * jnp.exp(cum[-1:]).T
                  + jnp.dot((k * decay_end).T, v,
                            preferred_element_type=jnp.float32))
    o_ref[0, ...] = (y_intra + y_inter).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 32, interpret: bool = False):
    """Same contract as ``ref.wkv6_ref`` (y only)."""
    B, S, H, K = r.shape
    Lc = min(chunk, S)
    pad = (-S) % Lc
    lw = jnp.log(jnp.clip(w.astype(jnp.float32), 1e-38, 1.0))

    def head_major(t):
        t = t.transpose(0, 2, 1, 3).reshape(B * H, S, K)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    rh, kh, vh, lwh = map(head_major, (r, k, v, lw))
    # padding must not decay the state: lw=0 ⇒ w=1 on padded steps
    Sp = S + pad
    nc = Sp // Lc
    uh = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    out = pl.pallas_call(
        functools.partial(_wkv_kernel, Lc=Lc),
        grid=(B * H, nc),
        in_specs=[
            pl.BlockSpec((1, Lc, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, K), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, ic: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Lc, K), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, K), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        interpret=interpret,
    )(rh, kh, vh, lwh, uh)
    return out[:, :S].reshape(B, H, S, K).transpose(0, 2, 1, 3)
