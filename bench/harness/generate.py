"""What the kinds' generators share: seeded streams, package variants
and random in-window partitions.

Everything here is plain numpy and knows nothing of the system under
test. The same seed always gives the same draws, and every seed the same
sizes, so seeds change what is asked, not how much.
"""
from __future__ import annotations

import itertools

import numpy as np


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per (seed, *stream): seeds may exceed 32 bits."""
    return np.random.default_rng([int(seed) & (2**63 - 1), *stream])


def derived_seed(seed: int, *stream: int) -> int:
    """A 31-bit seed for a solver call, drawn from (seed, *stream)."""
    return int(rng(seed, 7, *stream).integers(0, 2**31 - 1))


def variants(spec: dict) -> list[dict]:
    """Cartesian product of the package fields a traffic file varies,
    in the file's key order (later keys vary fastest)."""
    keys = list(spec)
    return [dict(zip(keys, vals))
            for vals in itertools.product(*(spec[k] for k in keys))]


# -------------------------------------------------------------- partitions
def _split_even(total: int, parts: int) -> np.ndarray:
    base, rem = divmod(int(total), parts)
    out = np.full(parts, base, dtype=np.int64)
    out[:rem] += 1
    return out


def _window(total: int, parts: int, unit: int, slack: int):
    """The Sec. 6.2 search window of one op on one axis, in units."""
    u = max(1, int(np.ceil(total / parts / unit)))
    floor = 1 if int(np.ceil(total / unit)) >= parts else 0
    return max(floor, u - slack), u + slack


def _repair(p: np.ndarray, total: int, unit: int, lo: int, hi: int):
    """Snap to multiples of ``unit`` inside the window, then restore the
    exact sum (the largest entry absorbs what units cannot)."""
    n = len(p)
    units = np.clip(np.round(p / unit).astype(np.int64), lo, hi)
    resid = total - int(units.sum()) * unit
    j = guard = 0
    while resid >= unit or resid <= -unit:
        guard += 1
        if guard > 10 * n * (hi - lo + 2):
            break
        k = j % n
        if resid > 0 and units[k] < hi:
            units[k] += 1
            resid -= unit
        elif resid < 0 and units[k] > lo:
            units[k] -= 1
            resid += unit
        j += 1
    vals = units * unit
    k = int(np.argmax(vals))
    vals[k] = max(0, vals[k] + total - int(vals.sum()))
    d = total - int(vals.sum())
    if d:
        vals[int(np.argmax(vals))] += d
    return vals


def base_partition(ops, pk, slack: int = 2):
    """The uniform (LS) partition projected into the solver's window."""
    Px = np.stack([_repair(_split_even(o.M, pk.X), o.M, pk.R,
                           *_window(o.M, pk.X, pk.R, slack)) for o in ops])
    Py = np.stack([_repair(_split_even(o.N, pk.Y), o.N, pk.C,
                           *_window(o.N, pk.Y, pk.C, slack)) for o in ops])
    return Px, Py


def _perturb_axis(g, base, totals, parts, unit, slack, count, steps):
    """``count`` copies of ``base`` [n, parts], each entry pair-moved by a
    whole number of units in [-steps, steps]: the sum of every row is
    kept exactly, and no entry leaves the wider of its start value and
    the Sec. 6.2 window."""
    P = np.repeat(base[None].astype(np.int64), count, axis=0)   # [c,n,k]
    win = np.array([_window(t, parts, unit, slack) for t in totals])
    lo = np.minimum(base, (win[:, :1] * unit))[None]
    hi = np.maximum(base, (win[:, 1:] * unit))[None]
    for start in (0, 1):
        a = np.arange(start, parts - 1, 2)
        if not len(a):
            continue
        b = a + 1
        d = g.integers(-steps, steps + 1, (count, len(totals), len(a))) * unit
        d = np.clip(d, np.maximum(lo[..., a] - P[..., a], P[..., b] - hi[..., b]),
                    np.minimum(hi[..., a] - P[..., a], P[..., b] - lo[..., b]))
        P[..., a] += d
        P[..., b] -= d
    return P


def partitions(g, ops, pk, count: int, steps: int = 2, slack: int = 2):
    """``count`` random in-window partitions ``(Px, Py, collectors)`` with
    shapes [c, n, X], [c, n, Y], [c, n]."""
    bx, by = base_partition(ops, pk, slack)
    Px = _perturb_axis(g, bx, [o.M for o in ops], pk.X, pk.R, slack, count,
                       steps)
    Py = _perturb_axis(g, by, [o.N for o in ops], pk.Y, pk.C, slack, count,
                       steps)
    co = g.integers(0, pk.Y, (count, len(ops)))
    return Px, Py, co


# ----------------------------------------------------------------- calls
#: Call index of the first warm-up call. A power of two, so that warm-up
#: call ``k`` has what window call ``k`` has of any cycle whose length
#: divides it (the objectives), on other inputs: the window's call
#: indices stay far below it.
WARM = 2**32
