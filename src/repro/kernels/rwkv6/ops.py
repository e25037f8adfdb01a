"""Public WKV-6 op: Pallas on TPU, chunked-XLA elsewhere."""
from __future__ import annotations

import jax

from .._common import check_pallas_backend
from .chunked import wkv6_chunked
from .kernel import wkv6 as wkv6_pallas
from .ref import wkv6_ref  # noqa: F401


def wkv6(r, k, v, w, u, *, chunk: int = 32,
         use_pallas: bool | None = None, interpret: bool = False):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas or interpret:
        check_pallas_backend(interpret)
        return wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=interpret)
    return wkv6_chunked(r, k, v, w, u, chunk=chunk)[0]
