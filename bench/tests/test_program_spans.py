"""The program's spans in a reduced trace (``bench.harness.program``): on a
synthetic trace with answers worked out by hand, and on pieces of traces
recorded on a TPU v5 lite, against a plain count over the nanoseconds."""
import json

import pytest

from bench.harness import program, trace
from bench.tests.conftest import ROOT
from bench.tests.test_trace import _covered

RECORDED = ROOT / "bench/tests/data/trace_program.json"

# Window 0..100 ns. Device ops [10,30), [60,70), so idle [0,10), [30,60),
# [70,100). Harness spans: sweep_call [0,80), generate [80,100). One
# program call, sweep.eval [2,78), holding the spans below.
LANES = {"dist": {"site": "dist", "lanes": 6, "events_sum": 9,
                  "events_max": 3, "fills_sum": 12, "fills_max": 4},
         "coll": {"site": "coll", "lanes": 6, "events_sum": 6,
                  "events_max": 2, "fills_sum": 6, "fills_max": 1}}
HAND = {"window": [0, 100],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 10, 20], ["copy.3", 60, 10]],
            "modules": [["jit__eval_single", 10, 20],
                        ["jit__eval_single", 60, 10]]}},
        "spans": [["sweep_call", 0, 80], ["generate", 80, 20]],
        "program": [["sweep.eval", 2, 76, {"call": 1, "points": 3}],
                    ["sweep.lookup", 2, 2, {"hits": 0, "misses": 3}],
                    ["sweep.group", 4, 2, {"groups": 1}],
                    ["sweep.consts", 6, 3, {"points": 3, "bytes": 96}],
                    ["eval.to_device", 9, 2, {"bytes": 96}],
                    ["eval.call", 11, 1, {}],
                    ["eval.fetch", 12, 23, {}],
                    ["eval.lanes", 36, 0, LANES["dist"]],
                    ["eval.lanes", 36, 0, LANES["coll"]],
                    ["sweep.records", 37, 18, {"records": 3}],
                    ["sweep.records", 56, 2, {"records": 3}]]}


def test_hand_trace():
    # preparation open and device idle: lookup 2 + group 2 + consts 3 +
    # records 18 + 2 = 27 of 100 ns
    assert program.prep_share(HAND) == pytest.approx(27.0)
    # to_device idle on [9,10), fetch idle on [30,35)
    assert program.transfer_share(HAND) == pytest.approx(6.0)
    # events (9 + 6) / (6 x 3 + 6 x 2); fills (12 + 6) / (6 x 4 + 6 x 1)
    assert program.lane_share(HAND) == pytest.approx(50.0)
    assert program.lane_share(HAND, "fills") == pytest.approx(60.0)
    # each idle nanosecond under its innermost span, harness spans where
    # no program span is open; 70 ns in all
    assert program.idle_by_span(HAND) == pytest.approx({
        "sweep.records": 20e-9, "generate": 20e-9, "sweep.eval": 13e-9,
        "eval.fetch": 5e-9, "sweep_call": 4e-9, "sweep.consts": 3e-9,
        "sweep.lookup": 2e-9, "sweep.group": 2e-9, "eval.to_device": 1e-9})
    # [30,60): sweep.records covers 18 of 30 ns, the innermost span over
    # half; [70,100): sweep.eval covers 8 of 30, so the harness's name
    # stays; [0,10): sweep.eval covers 8 of 10
    assert trace.idle_gaps(HAND) == [["sweep_call", 3e-8],
                                     ["generate", 3e-8],
                                     ["sweep_call", 1e-8]]
    assert program.idle_gaps(HAND) == [["sweep.records", 3e-8],
                                       ["generate", 3e-8],
                                       ["sweep.eval", 1e-8]]


def test_program_spans_change_no_harness_reading():
    bare = {k: v for k, v in HAND.items() if k != "program"}
    for f in (trace.busy_ns, trace.idle_share, trace.module_ns,
              trace.coverage, trace.top_ops, trace.idle_gaps):
        assert f(HAND) == f(bare)
    assert trace.idle_under(HAND, "sweep_call") == \
        trace.idle_under(bare, "sweep_call")


def test_without_program_spans_nothing_is_read():
    bare = {k: v for k, v in HAND.items() if k != "program"}
    for tr in (bare, {**bare, "program": []}):
        assert program.prep_share(tr) is None
        assert program.transfer_share(tr) is None
        assert program.lane_share(tr) is None
        assert program.idle_by_span(tr) is None
        assert program.idle_gaps(tr) == trace.idle_gaps(bare)


def test_small_keeps_the_piece_s_program_spans():
    piece = program.small(HAND, max_events=1)
    assert piece["window"] == [0, 30]
    assert [s[0] for s in piece["program"]] == [
        "sweep.eval", "sweep.lookup", "sweep.group", "sweep.consts",
        "eval.to_device", "eval.call", "eval.fetch"]


def _recorded():
    if not RECORDED.exists():
        return []
    return sorted(json.loads(RECORDED.read_text()).items())


@pytest.mark.parametrize("cell,tr", _recorded())
def test_recorded_trace_against_plain_count(cell, tr):
    lo, hi = tr["window"]
    ops = [(s, s + d) for _, s, d in tr["devices"]["/device:TPU:0"]["ops"]]
    win = hi - lo

    def idle_under(names):
        open_ = [(s, s + d) for n, s, d, _ in tr["program"] if n in names]
        both = [(max(a, c), min(b, d)) for a, b in open_ for c, d in ops
                if min(b, d) > max(a, c)]
        return 100.0 * (_covered(open_, lo, hi)
                        - _covered(both, lo, hi)) / win

    assert program.prep_share(tr) == pytest.approx(idle_under(program.PREP),
                                                   rel=1e-12, abs=1e-12)
    assert program.transfer_share(tr) == pytest.approx(
        idle_under(program.TRANSFER), rel=1e-12, abs=1e-12)
    idle_ns = win - _covered(ops, lo, hi)
    by_span = program.idle_by_span(tr)
    assert sum(by_span.values()) == pytest.approx(idle_ns / 1e9, rel=1e-9)
    names = {s[0] for s in tr["program"]} | {s[0] for s in tr["spans"]}
    assert set(by_span) <= names | {"none"}
    # the piece starts with the call's host preparation, which a program
    # span names
    gaps = program.idle_gaps(tr)
    assert gaps[0][0] in {s[0] for s in tr["program"]}
    assert [g[1] for g in gaps] == [g[1] for g in trace.idle_gaps(tr)]
