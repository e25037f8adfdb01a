"""Shared start of the tools: the checkout on ``sys.path``, a TPU, and the
persistent compile cache, as ``bench/run.py`` has them."""
from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def start(chips: int = 1):
    """Returns JAX's devices; exits nonzero without enough TPU chips."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"needs {chips} TPU chip(s); found {devs[0].platform!r}")
    from repro.runtime.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return devs


def seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]
