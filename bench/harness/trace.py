"""Profiler traces: capture, reduction to plain events, and the arithmetic
the per-layer metrics share.

A trace is reduced once to a small dict of plain events:

    {"window": [t0_ns, t1_ns],
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "spans": [[name, start_ns, dur_ns], ...]}

``ops`` are the device's operations, ``modules`` its executables, and
``spans`` the harness's own host annotations (names without the
``bench:`` prefix). All times are on the profiler's one clock. Every
function below reads only that dict, so a recorded trace saved in this
form checks them without a chip.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os

import numpy as np

PREFIX = "bench:"

@contextlib.contextmanager
def span(name: str):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(PREFIX + name):
        yield


def capture_start(directory: str) -> None:
    """Trace the device, and on the host only annotations such as the
    harness's spans: the runtime's own host events would cost the
    harness's Python path time that an untraced run does not spend."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)


def capture_stop(directory: str) -> str:
    """Stop the trace and return the path of its ``.xplane.pb``."""
    import jax

    jax.profiler.stop_trace()
    paths = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {directory}")
    return max(paths, key=os.path.getmtime)


def op_name(hlo: str) -> str:
    """An operation's short name: ``%while.474 = (...) while(...)`` is
    ``while.474``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def module_name(name: str) -> str:
    """An executable's name without its fingerprint: ``jit_chunk``."""
    return name.split("(", 1)[0]


def reduce_xplane(path: str) -> dict:
    """The plain-event form of one profiler trace (see module doc). The
    window is the harness's ``window`` span; each operation is named
    ``<executable>:<operation>``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: dict[str, dict] = {}
    spans = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [[e.name, int(e.start_ns),
                                  int(e.duration_ns)] for e in line.events]
                     for line in plane.lines}
            mods = sorted(([module_name(n), s, d]
                           for n, s, d in lines.get("XLA Modules", [])),
                          key=lambda m: m[1])
            starts = [m[1] for m in mods]
            ops = []
            for n, s, d in lines.get("XLA Ops", []):
                k = bisect.bisect_right(starts, s) - 1
                owner = mods[k][0] if k >= 0 and s < mods[k][1] + mods[k][2] \
                    else "?"
                ops.append([f"{owner}:{op_name(n)}", s, d])
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [[e.name[len(PREFIX):], int(e.start_ns),
                           int(e.duration_ns)]
                          for e in line.events if e.name.startswith(PREFIX)]
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("trace holds no window span")
    w = max(win, key=lambda s: s[2])
    return {"window": [w[1], w[1] + w[2]], "devices": devices,
            "spans": [s for s in spans if s[0] != "window"]}


# -------------------------------------------------------------- intervals
def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[int, int]]:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def complement(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in clip(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _events(evs) -> list[tuple[int, int]]:
    return [(s, s + d) for _, s, d in evs]


# --------------------------------------------------------------- readings
def window_ns(tr: dict) -> int:
    return tr["window"][1] - tr["window"][0]


def busy(tr: dict, device: str) -> list[tuple[int, int]]:
    """Intervals of the window in which some operation ran on the device
    (its executables where the trace has no per-operation line)."""
    d = tr["devices"][device]
    return clip(union(_events(d["ops"] or d["modules"])), *tr["window"])


def busy_ns(tr: dict) -> float:
    """Busy time in the window, averaged over the devices traced."""
    if not tr["devices"]:
        return 0.0
    return float(np.mean([length(busy(tr, d)) for d in tr["devices"]]))


def idle_share(tr: dict) -> float:
    return 1.0 - busy_ns(tr) / window_ns(tr)


def module_ns(tr: dict) -> float:
    """Device time of the executables in the window, averaged over the
    devices traced."""
    if not tr["devices"]:
        return 0.0
    return float(np.mean([
        length(clip(_events(d["modules"] or d["ops"]), *tr["window"]))
        for d in tr["devices"].values()]))


def span_intervals(tr: dict, name: str) -> list[tuple[int, int]]:
    return clip(union(_events(s for s in tr["spans"] if s[0] == name)),
                *tr["window"])


def idle_under(tr: dict, name: str) -> float:
    """Nanoseconds in which span ``name`` is open and the device idle,
    averaged over the devices traced."""
    open_ = span_intervals(tr, name)
    return float(np.mean([
        length(intersect(open_, complement(busy(tr, d), *tr["window"])))
        for d in tr["devices"]])) if tr["devices"] else 0.0


def coverage(tr: dict) -> tuple[int, float, float]:
    """The first device's operation count in the window, and the seconds
    from the window's start to its first and to its last operation's
    end: a device trace that stops early shows here."""
    if not tr["devices"]:
        return 0, 0.0, 0.0
    b = busy(tr, sorted(tr["devices"])[0])
    if not b:
        return 0, 0.0, 0.0
    lo = tr["window"][0]
    d = tr["devices"][sorted(tr["devices"])[0]]
    n = len(clip(_events(d["ops"] or d["modules"]), *tr["window"]))
    return n, (b[0][0] - lo) / 1e9, (b[-1][1] - lo) / 1e9


def top_ops(tr: dict, n: int = 10) -> list[list]:
    """The ``n`` device operations that took most time in the window,
    averaged over the devices traced: ``[[name, seconds], ...]``."""
    tot: dict[str, int] = {}
    lo, hi = tr["window"]
    for d in tr["devices"].values():
        for name, s, dur in d["ops"] or d["modules"]:
            c = min(s + dur, hi) - max(s, lo)
            if c > 0:
                tot[name] = tot.get(name, 0) + c
    k = max(len(tr["devices"]), 1)
    return [[name, t / k / 1e9] for name, t in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(tr: dict, n: int = 10) -> list[list]:
    """The ``n`` longest idle gaps of the first device in the window, each
    named by the harness span that covers most of it (a tie goes to the
    span open at the gap's start; ``none`` where no span is open):
    ``[[name, seconds], ...]``."""
    if not tr["devices"]:
        return []
    dev = sorted(tr["devices"])[0]
    gaps = complement(busy(tr, dev), *tr["window"])
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
    opened = {k: span_intervals(tr, k) for k in sorted({s[0] for s in
                                                        tr["spans"]})}
    out = []
    for g in gaps:
        def key(k):
            at_start = any(s <= g[0] < e for s, e in opened[k])
            return length(intersect([g], opened[k])), at_start
        best = max(opened, key=key, default=None)
        covered = best is not None and key(best)[0] > 0
        out.append([best if covered else "none", (g[1] - g[0]) / 1e9])
    return out


def small(tr: dict, max_events: int = 400) -> dict:
    """A short piece of a trace, from the window's start, kept whole
    enough to test the reduction on."""
    lo = tr["window"][0]
    cut = lo
    dev = {}
    for name, d in tr["devices"].items():
        ops = sorted(d["ops"], key=lambda e: e[1])[:max_events]
        if ops:
            cut = max(cut, ops[-1][1] + ops[-1][2])
        dev[name] = {"ops": ops, "modules": []}
    for name, d in tr["devices"].items():
        dev[name]["modules"] = [m for m in d["modules"] if m[1] < cut]
    return {"window": [lo, cut], "devices": dev,
            "spans": [s for s in tr["spans"] if s[1] < cut]}
