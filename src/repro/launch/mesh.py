"""Production meshes.

Single pod: (data=16, model=16) — a 16×16 TPU-v5e pod, 256 chips.
Multi-pod: (pod=2, data=16, model=16) — 512 chips; the "pod" axis is pure
data parallelism (DCN between pods carries only gradient reductions).

A FUNCTION, not a module constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).

Every mesh is built with ``Auto`` axes, the mode the sharding rules
(``launch/specs.py``, ``sharding/logical.py``) are written for:
``with_sharding_constraint`` accepts only ``Auto`` axes, and
``jax.make_mesh`` defaults to ``Explicit`` ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_devices: int | None = None, *, pod: bool = False):
    """Small mesh over the first ``n_devices`` host devices — used by
    tests, the train driver, and the sharded sweep fabric (DESIGN.md §15).

    Always carries the ``("data", "model")`` axes the launch-layer
    sharding rules (``launch/specs.py``) are written against (plus
    ``"pod"`` when ``pod=True`` applies). Shape resolution: ``pod=True``
    with ``n`` a multiple of 4 (and ≥ 8) gives the 3-axis
    ``(2, 2, n//4)`` pod mesh — the old code built that shape for ANY
    ``n ≥ 8`` and crashed whenever ``2·2·(n//4) != n`` (n=10, n=13, …);
    even ``n`` puts the factor of 2 on ``data`` — the old fallback gave
    n=2 the degenerate ``(1, 2)`` mesh whose dead ``data`` axis silently
    disabled data parallelism; odd ``n`` is ``(1, n)`` (a 2-way split
    does not exist).
    """
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        raise ValueError(f"make_debug_mesh: {n} devices requested but "
                         f"only {len(devs)} exist")
    devs = devs[:n]
    if pod and n >= 8 and n % 4 == 0:
        return _mesh((2, 2, n // 4), ("pod", "data", "model"), devs)
    d = 2 if n % 2 == 0 and n >= 2 else 1
    return _mesh((d, n // d), ("data", "model"), devs)
