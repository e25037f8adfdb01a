"""The float64 scope shared by every jitted engine, and the float64 ops
that need an order-exact form.

Every engine computes in float64 (cycle counts overflow float32
mantissas, DESIGN.md §5/§8), but the rest of the repo's JAX code is
float32. :func:`x64` turns float64 on for the duration of one engine
call only, so it never leaks into the global default.

:func:`cumsum_seq` adds strictly left to right, the same sequence of
IEEE additions as ``np.cumsum``, so it is bitwise equal to the numpy
references (the SGS priority contract of DESIGN.md §13 compares float
priorities exactly). XLA's own float64 ``cumsum`` takes minutes to
compile for a TPU, where float64 is emulated; the scan compiles in
about a second.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["x64", "cumsum_seq"]


def x64():
    """Context manager: float64 arrays inside, the global setting
    (float32 by default) untouched outside. The setting is
    thread-local, so a server worker thread gets its own scope."""
    return jax.enable_x64(True)


def cumsum_seq(x, axis: int = -1):
    """``np.cumsum(x, axis)``, bitwise: a sequential ``lax.scan`` whose
    carry starts at the first element (not at ``0.0 + x[0]``, which
    would turn ``-0.0`` into ``0.0``)."""
    x = jnp.moveaxis(x, axis, 0)
    if x.shape[0] == 0:
        return jnp.moveaxis(x, 0, axis)

    def step(acc, v):
        acc = acc + v
        return acc, acc

    _, rest = lax.scan(step, x[0], x[1:])
    return jnp.moveaxis(jnp.concatenate([x[:1], rest]), 0, axis)
