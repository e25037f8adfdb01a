"""chip_smoke.py on the CPU: it refuses to report without a TPU, and its
phases (every engine, every check) run end to end at a tiny size."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(grids=(4, 2), eval_partitions=1, ga_population=16,
                        ga_generations=2, miqp_budget=128,
                        pipeline_batches=(2, 3), cosearch_population=4,
                        cosearch_generations=2, served=16,
                        sharded_points=2)


def test_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_one_chip_phases_run_and_check_on_cpu(capsys):
    smoke = chip_smoke.Smoke(TINY)
    smoke.run_one_chip()
    assert set(smoke.timings) == {
        "eval_sweep[regime]", "eval_sweep[flow]", "solve_grid[ga]",
        "solve_grid[miqp]", "pipeline_sweep", "solve_grid[cosearch]",
        "optserver"}
    for t in smoke.timings.values():
        assert t["steady_compiles"] == 0
    out = capsys.readouterr().out
    assert "each bitwise equal to its solo call" in out


def test_four_chip_path_needs_four_devices():
    with pytest.raises(chip_smoke.SmokeFailure, match="needs 4 devices"):
        chip_smoke.Smoke(TINY).run_four_chips()
