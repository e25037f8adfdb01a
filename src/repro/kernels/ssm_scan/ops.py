"""Public SSD-scan op: Pallas on TPU, chunked-XLA elsewhere."""
from __future__ import annotations

import jax

from .._common import check_pallas_backend
from .chunked import ssm_scan_chunked
from .kernel import ssm_scan as ssm_scan_pallas
from .ref import ssm_scan_ref  # noqa: F401


def ssm_scan(x, dt, a, Bmat, Cmat, D, *, chunk: int = 128,
             use_pallas: bool | None = None, interpret: bool = False):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas or interpret:
        check_pallas_backend(interpret)
        return ssm_scan_pallas(x, dt, a, Bmat, Cmat, D, chunk=chunk,
                               interpret=interpret)
    return ssm_scan_chunked(x, dt, a, Bmat, Cmat, D, chunk=chunk)[0]
