"""The reduction from a trace to the per-layer readings: on a small
synthetic trace with answers worked out by hand, and on a piece of a
trace recorded on a TPU v5 lite, against a plain per-nanosecond count."""
import json

import pytest

from bench.harness import trace
from bench.tests.conftest import ROOT

RECORDED = ROOT / "bench/tests/data/trace_small.json"

# Window 0..100 ns. Device ops: [10,30), [20,40), [60,70). Spans:
# sweep_call [0,50), generate [50,60), server_wait [70,100).
HAND = {"window": [0, 100],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10, 20], ["fusion.2", 20, 20],
                    ["copy.3", 60, 10]],
            "modules": [["jit_chunk", 10, 30], ["jit_chunk", 60, 10]]}},
        "spans": [["sweep_call", 0, 50], ["generate", 50, 10],
                  ["server_wait", 70, 30]]}


def test_hand_trace():
    assert trace.busy(HAND, "/device:TPU:0") == [(10, 40), (60, 70)]
    assert trace.busy_ns(HAND) == 40
    assert trace.idle_share(HAND) == pytest.approx(0.6)
    assert trace.module_ns(HAND) == 40
    assert trace.coverage(HAND) == (3, 1e-8, 7e-8)
    # sweep_call open 0..50, device idle 0..10 and 40..50
    assert trace.idle_under(HAND, "sweep_call") == 20
    assert trace.top_ops(HAND) == [["fusion.1", 2e-8], ["fusion.2", 2e-8],
                                   ["copy.3", 1e-8]]
    # gaps [70,100), [40,60) (half sweep_call, half generate: the tie
    # goes to sweep_call), [0,10)
    assert trace.idle_gaps(HAND) == [["server_wait", 3e-8],
                                     ["sweep_call", 2e-8],
                                     ["sweep_call", 1e-8]]


def test_silent_tail_stays_in_the_window():
    """A device that goes quiet before the window closes is idle there:
    the window is the harness's, never cut to the last operation."""
    tr = json.loads(json.dumps(HAND))
    tr["window"] = [0, 2_000_000_100]
    assert trace.window_ns(tr) == 2_000_000_100
    assert trace.busy_ns(tr) == 40
    assert trace.coverage(tr) == (3, 1e-8, 7e-8)
    assert trace.idle_gaps(tr)[0] == ["server_wait", (2_000_000_100 - 70)
                                      / 1e9]


def _covered(intervals, lo, hi):
    """Nanoseconds of [lo, hi) covered by at least one interval, by a
    sweep over the sorted end points with a depth counter."""
    pts = sorted([(max(s, lo), 1) for s, e in intervals if e > lo and s < hi]
                 + [(min(e, hi), -1) for s, e in intervals
                    if e > lo and s < hi])
    depth, last, total = 0, lo, 0
    for t, d in pts:
        if depth > 0:
            total += t - last
        depth += d
        last = t
    return total


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace_against_sweep_count():
    tr = json.loads(RECORDED.read_text())
    lo, hi = tr["window"]
    assert list(tr["devices"]) == ["/device:TPU:0"]
    ops = [(s, s + d) for _, s, d in tr["devices"]["/device:TPU:0"]["ops"]]
    assert len(ops) == 300
    busy = _covered(ops, lo, hi)
    assert trace.busy_ns(tr) == busy
    calls = [(s, s + d) for n, s, d in tr["spans"] if n == "sweep_call"]
    idle_in_call = _covered(calls, lo, hi) - _covered(
        [(max(a, c), min(b, d)) for a, b in calls for c, d in ops
         if min(b, d) > max(a, c)], lo, hi)
    assert trace.idle_under(tr, "sweep_call") == idle_in_call
    assert 0.0 <= trace.idle_share(tr) <= 1.0
    gaps = trace.idle_gaps(tr)
    assert gaps == sorted(gaps, key=lambda g: -g[1])
    assert all(name.startswith("jit_")
               for name, _ in trace.top_ops(tr))
