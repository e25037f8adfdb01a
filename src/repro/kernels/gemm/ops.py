"""jit'd public wrapper: Pallas on TPU, jnp elsewhere."""
from __future__ import annotations

import jax

from .._common import check_pallas_backend
from .kernel import matmul as matmul_pallas
from .ref import matmul_ref


def matmul(a, b, *, use_pallas: bool | None = None, interpret: bool = False):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas or interpret:
        check_pallas_backend(interpret)
        return matmul_pallas(a, b, interpret=interpret)
    return matmul_ref(a, b)
