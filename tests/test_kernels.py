"""Pallas kernel validation (interpret mode on CPU): shape/dtype sweeps
against the pure-jnp ref.py oracles, per the assignment contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gemm.kernel import matmul
from repro.kernels.gemm.ref import matmul_ref
from repro.kernels.rwkv6.kernel import wkv6
from repro.kernels.rwkv6.ref import wkv6_ref
from repro.kernels.ssm_scan.kernel import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_ref

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------- gemm
@pytest.mark.parametrize("m,k,n", [(128, 128, 128), (256, 512, 128),
                                   (100, 70, 130), (33, 257, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_sweep(m, k, n, dtype):
    a = jax.random.normal(KEY, (m, k), dtype)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (k, n), dtype)
    got = matmul(a, b, bm=64, bn=64, bk=64, interpret=True)
    want = matmul_ref(a, b)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 8)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(8, 200), k=st.integers(8, 200), n=st.integers(8, 200))
def test_gemm_property(m, k, n):
    a = jax.random.normal(KEY, (m, k), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 2), (k, n), jnp.float32)
    got = matmul(a, b, bm=64, bn=64, bk=64, interpret=True)
    np.testing.assert_allclose(got, matmul_ref(a, b), rtol=2e-5,
                               atol=1e-4)


# ------------------------------------------------------ flash attention
@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=37),
    dict(causal=True, softcap=30.0),
    dict(causal=True, window=64, softcap=50.0),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_variants(kwargs, dtype):
    B, S, H, KV, Dh = 2, 130, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, Dh), dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, Dh),
                          dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, Dh),
                          dtype)
    got = flash_attention(q, k, v, bq=32, bk=48, interpret=True, **kwargs)
    want = attention_ref(q, k, v, **kwargs)
    tol = 3e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 4)


def test_flash_attention_mla_shapes():
    """MLA absorbed form: k-dim != v-dim, MQA (KV=1), custom scale."""
    B, S, H = 2, 96, 8
    dk, dv = 80, 64
    q = jax.random.normal(KEY, (B, S, H, dk), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, 1, dk),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, 1, dv),
                          jnp.float32)
    got = flash_attention(q, k, v, causal=True, scale=0.125, bq=32, bk=32,
                          interpret=True)
    want = attention_ref(q, k, v, causal=True, scale=0.125)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-4)


@settings(max_examples=6, deadline=None)
@given(s=st.integers(16, 160), h=st.sampled_from([2, 4, 6]),
       g=st.sampled_from([1, 2]))
def test_flash_attention_property(s, h, g):
    B, Dh = 1, 16
    kv = max(1, h // g)
    h = kv * g
    q = jax.random.normal(KEY, (B, s, h, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 5), (B, s, kv, Dh),
                          jnp.float32)
    v = jax.random.normal(jax.random.fold_in(KEY, 6), (B, s, kv, Dh),
                          jnp.float32)
    got = flash_attention(q, k, v, causal=True, bq=32, bk=32,
                          interpret=True)
    want = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=1e-4)


# -------------------------------------------------------------- ssm scan
@pytest.mark.parametrize("s,chunk", [(64, 32), (100, 32), (256, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_sweep(s, chunk, dtype):
    B, H, P, G, N = 2, 4, 16, 2, 8
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (B, s, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, s, H))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    Bm = jax.random.normal(ks[3], (B, s, G, N), dtype)
    Cm = jax.random.normal(ks[4], (B, s, G, N), dtype)
    D = jax.random.normal(ks[5], (H,)) * 0.1
    got = ssm_scan(x, dt, a, Bm, Cm, D, chunk=chunk, interpret=True)
    want, _ = ssm_scan_ref(x, dt, a, Bm, Cm, D)
    tol = 2e-3 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ----------------------------------------------------------------- rwkv6
@pytest.mark.parametrize("s,chunk", [(32, 16), (70, 16), (128, 32)])
def test_wkv6_sweep(s, chunk):
    B, H, K = 2, 3, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, s, H, K))
    k = jax.random.normal(ks[1], (B, s, H, K))
    v = jax.random.normal(ks[2], (B, s, H, K))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, s, H, K)) * 0.5))
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    got = wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    want, _ = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_wkv6_bf16():
    B, s, H, K = 1, 48, 2, 16
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, s, H, K), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, s, H, K), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, s, H, K), jnp.bfloat16)
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, s, H, K)) * 0.5)
                ).astype(jnp.bfloat16)
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    got = wkv6(r, k, v, w, u, chunk=16, interpret=True)
    want, _ = wkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=6e-2, atol=6e-2)


# ------------------------------------------------------- ops dispatch
def _ops_calls():
    from repro.kernels.flash_attention.ops import attention
    from repro.kernels.gemm.ops import matmul as matmul_op
    from repro.kernels.rwkv6.ops import wkv6 as wkv6_op
    from repro.kernels.ssm_scan.ops import ssm_scan as ssm_scan_op

    x4 = jnp.ones((1, 16, 2, 8))
    return {
        "gemm": lambda **kw: matmul_op(jnp.ones((8, 8)), jnp.ones((8, 8)),
                                       **kw),
        "flash_attention": lambda **kw: attention(x4, x4, x4, **kw),
        "rwkv6": lambda **kw: wkv6_op(x4, x4, x4, 0.5 * x4,
                                      jnp.ones((2, 8)), chunk=8, **kw),
        "ssm_scan": lambda **kw: ssm_scan_op(
            x4, jnp.ones((1, 16, 2)), -jnp.ones((2,)), x4[:, :, :1],
            x4[:, :, :1], jnp.ones((2,)), chunk=8, **kw),
    }


@pytest.mark.parametrize("op", ["gemm", "flash_attention", "rwkv6",
                                "ssm_scan"])
def test_ops_refuse_pallas_off_tpu_without_interpret(op):
    """Off a TPU, asking for the Pallas kernel raises unless the caller
    asks for interpret mode; the default picks the XLA path."""
    assert jax.default_backend() != "tpu"
    call = _ops_calls()[op]
    with pytest.raises(RuntimeError, match="interpret=True"):
        call(use_pallas=True)
    np.testing.assert_allclose(call(use_pallas=True, interpret=True),
                               call(), rtol=2e-3, atol=2e-3)
