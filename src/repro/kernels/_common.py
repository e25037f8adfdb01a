"""Helpers shared by the Pallas kernels and their ``ops.py`` wrappers."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def check_pallas_backend(interpret: bool) -> None:
    """A compiled Pallas kernel needs a TPU. Off a TPU the caller must
    ask for interpret mode itself (``interpret=True``), so that a run
    never times the interpreter while believing it timed the chip."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"Pallas kernel requested on backend "
            f"{jax.default_backend()!r}, which is not a TPU; pass "
            f"interpret=True to run it in interpret mode")


def cumsum_rows(x):
    """Inclusive cumulative sum over axis 0 of a 2-D float32 tile, as a
    lower-triangular matmul: Mosaic has no ``cumsum`` lowering. HIGHEST
    precision keeps the MXU from rounding the f32 operands to bf16."""
    n = x.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    return jnp.dot(tri.astype(jnp.float32), x,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
