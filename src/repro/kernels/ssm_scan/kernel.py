"""Pallas TPU kernel for the Mamba-2 SSD chunk scan.

Grid (B·H, n_chunks) with chunks innermost: the (P, N) chunk state lives
in VMEM scratch and is carried across the sequential chunk steps of each
(batch, head) program — the TPU-native mapping of the paper-style
"SIMD-class sequential op": intra-chunk work is dense MXU matmuls, the
recurrence is the tiny VMEM-resident state update.

Inputs are pre-arranged head-major and the per-head decay increments
``da = dt·a`` are precomputed, so the kernel sees only 2-D tiles:
  x  (BH, L, P)    dt (BH, L, 1)    da (BH, L, 1)
  Bm (BH, L, N)    Cm (BH, L, N)    (KV groups pre-broadcast to heads)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._common import cumsum_rows


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, o_ref, h_ref, *,
                n_chunks: int, Lc: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)        # (Lc, P)
    dt = dt_ref[0].astype(jnp.float32)      # (Lc, 1)
    da = da_ref[0].astype(jnp.float32)      # (Lc, 1)
    Bm = b_ref[0].astype(jnp.float32)       # (Lc, N)
    Cm = c_ref[0].astype(jnp.float32)       # (Lc, N)

    cum = cumsum_rows(da)                   # (Lc, 1)
    # intra-chunk: y[i] = Σ_{j<=i} exp(cum_i - cum_j)·dt_j·(C_i·B_j)·x_j
    diff = cum - cum.T                      # (Lc, Lc)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (Lc, Lc), 1))
    Lmat = jnp.where(tri, jnp.exp(diff), 0.0)
    CB = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    W = CB * Lmat * dt.T                    # (Lc, Lc), weight on x_j
    y = jnp.dot(W, x, preferred_element_type=jnp.float32)
    # inter-chunk: y[i] += (C_i·exp(cum_i)) @ h   (h: (N, P))
    y += jnp.dot(Cm * jnp.exp(cum), h_ref[...],
                 preferred_element_type=jnp.float32)
    # state update: h' = exp(cum_L)·h + Σ_j exp(cum_L - cum_j)·dt_j·B_j⊗x_j
    decay_end = jnp.exp(cum[-1:] - cum)     # (Lc, 1)
    dB = Bm * (dt * decay_end)              # (Lc, N)
    # exp(cum_L) as a scalar: Mosaic cannot broadcast a (1, 1) tile over
    # both sublanes and lanes.
    h_ref[...] = (h_ref[...] * jnp.exp(jnp.sum(da))
                  + jnp.dot(dB.T, x, preferred_element_type=jnp.float32))
    o_ref[0, ...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan(x, dt, a, Bmat, Cmat, D, *, chunk: int = 128,
             interpret: bool = False):
    """Same contract as ``ref.ssm_scan_ref`` (returns y only — the final
    state stays device-side in serving, which uses the decode step)."""
    Bsz, S, H, P = x.shape
    G, N = Bmat.shape[2], Bmat.shape[3]
    rep = H // G
    Lc = min(chunk, S)
    pad = (-S) % Lc
    dt32 = dt.astype(jnp.float32)
    da = dt32 * a.astype(jnp.float32)[None, None, :]

    def head_major(t, feat):
        t = t.transpose(0, 2, 1, 3) if t.ndim == 4 else \
            t.transpose(0, 2, 1)[..., None]
        t = t.reshape(Bsz * H, S, feat)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    xh = head_major(x, P)
    dth = head_major(dt32, 1)
    dah = head_major(da, 1)
    Bh = head_major(jnp.repeat(Bmat, rep, axis=2), N)
    Ch = head_major(jnp.repeat(Cmat, rep, axis=2), N)
    Sp = S + pad
    nc = Sp // Lc

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc, Lc=Lc),
        grid=(Bsz * H, nc),
        in_specs=[
            pl.BlockSpec((1, Lc, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, Lc, N), lambda bh, ic: (bh, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, Lc, P), lambda bh, ic: (bh, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((Bsz * H, Sp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xh, dth, dah, Bh, Ch)
    y = out[:, :S].reshape(Bsz, H, S, P).transpose(0, 2, 1, 3)
    return y + x * D[None, None, :, None]
