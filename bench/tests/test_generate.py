"""The generators of the traffic kinds: the same seed gives the same
inputs, and every seed the same amount of work."""
import json

import pytest

from bench.harness import generate as gen
from bench.harness import manifest
from bench.reference import mcm
from bench.tests.conftest import ROOT

BIG = 2**31 + 987654321


def load(path):
    return json.loads((ROOT / path).read_text())


VIT = load("bench/configs/vit_b16.a16x16_hbm.json")
ALEX = load("bench/tests/data/alexnet.a4x4_hbm.json")
GA = load("bench/traffic/ga_islands.json")
FLOW = load("bench/traffic/flow_eval.json")


def kind(traffic):
    return manifest.module(ROOT, "kinds", traffic["kind"])


def test_ga_calls_deterministic_and_fixed_budget():
    k = kind(GA)
    a, b = k.call(GA, VIT, BIG, 3), k.call(GA, VIT, BIG, 3)
    assert a == b
    assert a["designs"] == 8 * 64 * 60 and len(a["variants"]) == 8
    assert k.call(GA, VIT, BIG + 1, 3)["designs"] == a["designs"]
    assert {k.call(GA, VIT, BIG, i)["objective"]
            for i in range(2)} == {"edp", "latency"}
    warm = k.warm_calls(GA, VIT, BIG)
    assert [c["objective"] for c in warm] == ["edp", "latency"]
    seeds = {c["solver"]["seed"] for c in warm}
    assert not seeds & {k.call(GA, VIT, BIG, i)["solver"]["seed"]
                        for i in range(50)}


def test_flow_calls_deterministic_and_fixed_size():
    k = kind(FLOW)
    a, b = k.call(FLOW, ALEX, BIG, 2), k.call(FLOW, ALEX, BIG, 2)
    assert (a["Px"] == b["Px"]).all() and (a["collectors"]
                                           == b["collectors"]).all()
    c = k.call(FLOW, ALEX, BIG + 1, 2)
    assert c["Px"].shape == a["Px"].shape and (c["Px"] != a["Px"]).any()
    assert a["designs"] == len(a["Px"]) == FLOW["points"]
    assert (k.warm_calls(FLOW, ALEX, BIG)[0]["Px"] != a["Px"]).any()


@pytest.mark.parametrize("cfg", [VIT, ALEX], ids=["vit16", "alex4"])
def test_partitions_are_valid_and_deterministic(cfg):
    ops, pk = mcm.graph_ops(cfg["workload"]), mcm.package(cfg)
    Px, Py, co = gen.partitions(gen.rng(BIG, 1), ops, pk, 32)
    Px2, _, _ = gen.partitions(gen.rng(BIG, 1), ops, pk, 32)
    assert (Px == Px2).all()
    ref = mcm.Reference(ops, pk, cfg["options"])
    for k in range(32):
        assert ref.check_partition(Px[k], Py[k], co[k]) is None
    assert len({Px[k].tobytes() for k in range(32)}) == 32
