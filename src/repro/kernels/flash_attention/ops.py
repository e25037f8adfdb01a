"""Public attention op: Pallas flash kernel on TPU, blockwise-XLA
elsewhere; ``attention_ref`` is the O(S²) oracle for tests."""
from __future__ import annotations

import jax

from .._common import check_pallas_backend
from .blockwise import blockwise_attention
from .kernel import flash_attention
from .ref import attention_ref  # noqa: F401


def attention(q, k, v, *, causal=True, window=None, softcap=None,
              scale=None, use_pallas: bool | None = None,
              interpret: bool = False, **kw):
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas or interpret:
        check_pallas_backend(interpret)
        return flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, interpret=interpret)
    return blockwise_attention(q, k, v, causal=causal, window=window,
                               softcap=softcap, scale=scale, **kw)
