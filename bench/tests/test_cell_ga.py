"""The vit16.ga_islands cell at a CPU size: a sound run is correct; the control (the
engines in float32) and every fault this cell can have are not."""
import pytest

from bench.tests import tiny
from bench.harness import faults

W = "vit16.ga_islands"


def test_sound_run_is_correct():
    out = tiny.run(W)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"


def test_control_is_not_correct():
    out = tiny.run(W, whole=faults.float32())
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "no_search", "half_batch", "altered_answer"])
def test_fault_is_not_correct(fault):
    out = tiny.run(W, timed_patch=faults.FAULTS[fault]())
    assert not out["correct"], out["checks"]
