"""The program's own spans in a profiler trace, and the readings built on
them.

The program names its work with ``jax.profiler.TraceAnnotation``s called
``mcm:<name>`` (``src/repro/runtime/spans.py``; the spans and their
arguments are listed in PERF.md section 3). This module adds them to the
plain-event form of ``bench.harness.trace`` under the key ``program``:

    "program": [[name, start_ns, dur_ns, {stat: value, ...}], ...]

(names without the prefix, in start order). ``spans`` and every reading
of ``bench.harness.trace`` stay as they are. Each reading here returns
``None`` where the trace holds no program spans, as a trace of a program
without them does. :data:`PREP` are the sweep driver's host preparation,
:data:`TRANSFER` the engines' copies and syncs; ``eval.lanes`` markers
carry the flow netsim's lane counts.
"""
from __future__ import annotations

import warnings

from bench.harness import trace

PREFIX = "mcm:"

PREP = ("sweep.lookup", "sweep.group", "sweep.consts", "sweep.records",
        "ga.consts", "ga.init", "ga.results")
TRANSFER = ("eval.to_device", "eval.fetch", "ga.to_device", "ga.chunk")


def reduce_spans(path: str) -> list[list]:
    """The program's spans in one ``.xplane.pb``, with their stats."""
    from jax.profiler import ProfileData

    out = []
    with warnings.catch_warnings():
        # iterating a stats view warns about its builtin type's module
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                out += [[e.name[len(PREFIX):], int(e.start_ns),
                         int(e.duration_ns), dict(e.stats)]
                        for e in line.events if e.name.startswith(PREFIX)]
    return sorted(out, key=lambda s: s[1])


def reduce_xplane(path: str) -> dict:
    """``bench.harness.trace.reduce_xplane`` plus the program's spans."""
    return {**trace.reduce_xplane(path), "program": reduce_spans(path)}


def _program(tr: dict) -> list | None:
    return tr.get("program") or None


def _open(tr: dict, names) -> list[tuple[int, int]]:
    return trace.clip(trace.union((s, s + d) for n, s, d, _ in tr["program"]
                                  if n in names), *tr["window"])


def _idle(tr: dict, device: str) -> list[tuple[int, int]]:
    return trace.complement(trace.busy(tr, device), *tr["window"])


def idle_share_under(tr: dict, names) -> float | None:
    """Percent of the window in which one of the program spans ``names``
    is open and the device idle, averaged over the devices traced."""
    if _program(tr) is None or not tr["devices"]:
        return None
    open_ = _open(tr, names)
    ns = sum(trace.length(trace.intersect(open_, _idle(tr, d)))
             for d in tr["devices"]) / len(tr["devices"])
    return 100.0 * ns / trace.window_ns(tr)


def prep_share(tr: dict) -> float | None:
    """The device waits on the sweep driver's host preparation."""
    return idle_share_under(tr, PREP)


def transfer_share(tr: dict) -> float | None:
    """The device waits on the engines' copies and syncs."""
    return idle_share_under(tr, TRANSFER)


def lane_share(tr: dict, count: str = "events") -> float | None:
    """Over the window's ``eval.lanes`` markers, the lanes' iterations
    against the lockstep ones: sum of ``<count>_sum`` / sum of ``lanes``
    x ``<count>_max``, in percent. Exact for ``events``, the vmapped
    event loop; an upper bound for ``fills``, whose lockstep count in
    each event is the slowest lane's of that event."""
    if _program(tr) is None:
        return None
    lo, hi = tr["window"]
    marks = [a for n, s, _, a in tr["program"]
             if n == "eval.lanes" and lo <= s <= hi]
    lockstep = sum(a["lanes"] * a[f"{count}_max"] for a in marks)
    if not lockstep:
        return None
    return 100.0 * sum(a[f"{count}_sum"] for a in marks) / lockstep


def _labelled(tr: dict) -> list[tuple[int, int, str]]:
    """The window cut into pieces, each labelled with the innermost span
    open there: a program span if one is open, else a harness span, else
    ``none``. Innermost is shortest; spans of one thread nest."""
    lo, hi = tr["window"]
    spans = [(s, s + d, n, 0) for n, s, d, _ in tr["program"]] + \
        [(s, s + d, n, 1) for n, s, d in tr["spans"]]
    cuts = sorted({lo, hi} | {t for s, e, _, _ in spans
                              for t in (s, e) if lo < t < hi})
    starts = sorted(spans)
    out, live, k = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][0] <= a:
            live.append(starts[k])
            k += 1
        live = [sp for sp in live if sp[1] > a]
        best = min(live, key=lambda sp: (sp[3], sp[1] - sp[0], -sp[0]),
                   default=None)
        out.append((a, b, best[2] if best else "none"))
    return out


def idle_by_span(tr: dict) -> dict[str, float] | None:
    """Seconds of the first device's idle time in the window under each
    innermost span (see :func:`_labelled`), largest first."""
    if _program(tr) is None or not tr["devices"]:
        return None
    idle = _idle(tr, sorted(tr["devices"])[0])
    tot: dict[str, int] = {}
    i = 0
    for a, b, name in _labelled(tr):
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        j = i
        while j < len(idle) and idle[j][0] < b:
            c = min(b, idle[j][1]) - max(a, idle[j][0])
            if c > 0:
                tot[name] = tot.get(name, 0) + c
            j += 1
    return {n: t / 1e9 for n, t in sorted(tot.items(), key=lambda kv: -kv[1])}


def idle_gaps(tr: dict, n: int = 10) -> list[list]:
    """``bench.harness.trace.idle_gaps``, each gap renamed by the innermost
    program span that covers more than half of it; a gap no program span
    covers so keeps the harness's name."""
    gaps = trace.idle_gaps(tr, n)
    if _program(tr) is None or not gaps:
        return gaps
    dev = sorted(tr["devices"])[0]
    ivs = sorted(_idle(tr, dev), key=lambda g: g[0] - g[1])[:n]
    out = []
    for (name, secs), (a, b) in zip(gaps, ivs):
        over = [(e - s, -s, n_) for n_, s, d, _ in tr["program"]
                for e in [s + d]
                if 2 * (min(b, e) - max(a, s)) > b - a]
        out.append([min(over)[2] if over else name, secs])
    return out


def small(tr: dict, max_events: int = 400) -> dict:
    """``bench.harness.trace.small`` with the program spans that start in
    the piece."""
    piece = trace.small(tr, max_events)
    piece["program"] = [s for s in tr.get("program", [])
                        if s[1] < piece["window"][1]]
    return piece
