"""Checkpointed sweep resume (DESIGN.md §15): periodic cache
persistence through ``serve.cache_store``, kill/restart recovery with
exact hit accounting, straggler flagging, and the run_grid progress
line. The chaos test kills a real child process mid-grid (``os._exit``
after K checkpoint appends — no cleanup, no atexit) and asserts the
restarted run recomputes only the tail, bitwise-identically to an
uninterrupted run. Children use the numpy backend: no jax import, so
they start in milliseconds."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (CoSearchConfig, EvalOptions, GemmOp,
                        MultiTenantConfig, Task, make_hw)
from repro.core import sweep
from repro.core.ga import GAConfig
from repro.core.miqp import MIQPConfig
from repro.core.netsim import MeshNet
from repro.core.pipelining import PipelineConfig
from repro.runtime.fault_tolerance import StragglerMonitor

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


def toy_task(n=3, m=512):
    ops = [GemmOp("g0", M=m, K=256, N=512)]
    for i in range(1, n):
        ops.append(GemmOp(f"g{i}", M=m, K=ops[-1].N, N=512, chained=True))
    return Task(f"toy{n}_{m}", ops)


@pytest.fixture(autouse=True)
def _fresh_cache():
    sweep.clear_cache()
    yield
    sweep.clear_cache()


def _points(k=6):
    task = toy_task(3)
    return [sweep.EvalPoint(task, make_hw("A", 4, "hbm", bw_nop=64.0 + i),
                            EvalOptions(redistribution=True))
            for i in range(k)]


# ------------------------------------------------- in-process semantics
GA_CFG = GAConfig(population=16, generations=2, seed=1)
CO_CFG = CoSearchConfig(population=4, generations=2, seed_steps=2,
                        seed_starts=2)
MIQP_CFG = MIQPConfig(engine="lattice", backend="numpy")
MT_CFG = MultiTenantConfig(method="uniform")
PIPE_CFG = PipelineConfig(engine="vectorized", backend="numpy")
FAMILIES = ["eval", "netsim", "ga", "miqp", "cosearch", "multitenant",
            "pipeline"]


def _family(name):
    """One sweep family's ``(points, run, fingerprint)``: ``run(points,
    **kw)`` sweeps them on the host (co-search on a small jax config, its
    only backend) and ``fingerprint(pt)`` is the cache key a point's
    record is stored under."""
    pts = _points(4)
    if name == "eval":
        return (pts,
                lambda p, **kw: sweep.eval_sweep(p, backend="numpy", **kw),
                lambda pt: sweep._point_fingerprint(pt, "numpy"))
    if name == "netsim":
        return ([MeshNet(2, 2, 64.0 + i, 128.0, [0]) for i in range(4)],
                lambda p, **kw: sweep.netsim_sweep(p, 1e6, backend="numpy",
                                                   **kw),
                lambda net: sweep._netsim_fingerprint(net, 1e6, "numpy"))
    if name == "ga":
        return (pts,
                lambda p, **kw: sweep.solve_grid(p, "latency", GA_CFG,
                                                 backend="numpy", **kw),
                lambda pt: sweep._solver_fingerprint(pt, "ga", "numpy",
                                                     "latency", GA_CFG))
    if name == "miqp":
        return (pts,
                lambda p, **kw: sweep.solve_grid(p, "latency", MIQP_CFG,
                                                 backend="numpy",
                                                 method="miqp", **kw),
                lambda pt: sweep._solver_fingerprint(pt, "miqp", "numpy",
                                                     "latency", MIQP_CFG))
    if name == "cosearch":
        # odd points have diagonal links: the sweep keys them as plain
        diag = [sweep.EvalPoint(pt.task, dataclasses.replace(
            pt.hw, diagonal_links=bool(i % 2)), pt.options)
            for i, pt in enumerate(pts)]
        return (diag,
                lambda p, **kw: sweep.cosearch_sweep(p, "edp", CO_CFG, **kw),
                lambda pt: sweep._solver_fingerprint(
                    dataclasses.replace(pt, hw=dataclasses.replace(
                        pt.hw, diagonal_links=False)),
                    "cosearch", "jax", "edp", CO_CFG))
    if name == "multitenant":
        tenants = (toy_task(2, 256), toy_task(2, 512))
        return ([sweep.MultiTenantPoint(tenants, pt.hw) for pt in pts],
                lambda p, **kw: sweep.multitenant_sweep(
                    p, "edp", MT_CFG, backend="numpy", **kw),
                lambda pt: sweep._multitenant_fingerprint(pt, "numpy", "edp",
                                                          MT_CFG))
    assert name == "pipeline"
    return ([sweep.PipelinePoint([(f"g{j}", 1.0 + i, 2.0, 0.5 * j)
                                  for j in range(3)], 4) for i in range(4)],
            lambda p, **kw: sweep.pipeline_sweep(p, PIPE_CFG, **kw),
            lambda pt: sweep._pipeline_fingerprint(pt, PIPE_CFG))


def _bitwise(a, b) -> bool:
    """Records equal down to the last bit, whatever their family."""
    if isinstance(a, np.ndarray):
        return (a.dtype == b.dtype and a.shape == b.shape
                and a.tobytes() == b.tobytes())
    if isinstance(a, float):
        return isinstance(b, float) and a.hex() == b.hex()
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_bitwise(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(map(_bitwise, a, b))
    if dataclasses.is_dataclass(a):
        return type(a) is type(b) and all(
            _bitwise(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    return a == b


@pytest.mark.parametrize("family", FAMILIES)
def test_checkpoint_requires_cache(family, tmp_path):
    points, run, _ = _family(family)
    with pytest.raises(ValueError, match="cache=True"):
        run(points[:2], cache=False, checkpoint=str(tmp_path / "store.bin"))


@pytest.mark.parametrize("family", FAMILIES)
def test_checkpoint_roundtrip(family, tmp_path):
    """Every family resumes from its store: a second run is all hits, its
    records bitwise the first run's; the straggler monitor sees each
    checkpoint chunk."""
    path = str(tmp_path / "store.bin")
    points, run, _ = _family(family)
    mon = StragglerMonitor()
    recs = run(points, checkpoint=path, checkpoint_every=2, straggler=mon)
    assert mon.ewma > 0            # observed per-chunk wall-times
    if family != "multitenant":    # its inner sweeps count lookups too
        assert sweep.cache_stats() == {"hits": 0, "misses": len(points)}
    sweep.clear_cache()
    recs2 = run(points, checkpoint=path, checkpoint_every=2)
    # the store held every record: pure resume, zero recomputation
    assert sweep.cache_stats() == {"hits": len(points), "misses": 0}
    assert all(r is not None for r in recs2)
    assert all(_bitwise(a, b) for a, b in zip(recs, recs2))


@pytest.mark.parametrize("family", FAMILIES)
def test_cache_keys_are_family_fingerprints(family):
    """The keys a sweep leaves in the cache are its family's fingerprints
    of the points, so a store written by an earlier driver still
    resumes."""
    points, run, fingerprint = _family(family)
    run(points)
    keys = set(sweep.export_cache())
    fps = {fingerprint(pt) for pt in points}
    assert len(fps) == len(points)
    tag = next(iter(fps))[0]
    assert {k for k in keys if k[0] == tag} == fps
    if family != "multitenant":    # its inner sweeps store their own
        assert keys == fps


def test_partial_store_resumes_tail_only(tmp_path):
    path = str(tmp_path / "store.bin")
    pts = _points(6)
    sweep.eval_sweep(pts[:4], backend="numpy", checkpoint=path)
    sweep.clear_cache()
    sweep.eval_sweep(pts, backend="numpy", checkpoint=path)
    assert sweep.cache_stats() == {"hits": 4, "misses": 2}


def test_straggler_flag_emits_stderr_line(tmp_path, capsys):
    class AlwaysSlow:
        def observe(self, step, dt):
            return True

    sweep.eval_sweep(_points(4), backend="numpy",
                     checkpoint=str(tmp_path / "s.bin"),
                     checkpoint_every=2, straggler=AlwaysSlow())
    assert "straggler" in capsys.readouterr().err


def test_run_grid_progress_and_checkpoint(tmp_path, capsys):
    path = str(tmp_path / "store.bin")
    pts = _points(3)
    out = sweep.run_grid(
        [{"i": i} for i in range(3)],
        lambda i: sweep.eval_sweep([pts[i]], backend="numpy")[0],
        progress="grid", checkpoint=path)
    assert len(out) == 3
    err = capsys.readouterr().err
    # liveness goes to stderr: label, counter, rate, ETA
    assert "grid point 3/3" in err
    assert "pts/s" in err and "eta" in err
    sweep.clear_cache()
    sweep.eval_sweep(pts, backend="numpy", checkpoint=path)
    assert sweep.cache_stats() == {"hits": 3, "misses": 0}


# ----------------------------------------------------- chaos kill/resume
_CHILD_PRELUDE = """
    import os
    import numpy as np
    from repro.core import sweep, EvalOptions, GemmOp, Task, make_hw

    def points():
        ops = [GemmOp("g0", M=512, K=256, N=512)]
        for i in range(1, 3):
            ops.append(GemmOp(f"g{i}", M=512, K=ops[-1].N, N=512,
                              chained=True))
        task = Task("toy3", ops)
        return [sweep.EvalPoint(
                    task, make_hw("A", 4, "hbm", bw_nop=64.0 + i),
                    EvalOptions(redistribution=True))
                for i in range(6)]

    def digest(recs):
        return "|".join(float(r["latency"]).hex() + ":" +
                        r["t_in"].tobytes().hex() for r in recs)
"""


def _run_child(body: str, store: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["STORE"] = store
    script = textwrap.dedent(_CHILD_PRELUDE) + textwrap.dedent(body)
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=120)


def test_chaos_kill_midgrid_then_resume(tmp_path):
    store = str(tmp_path / "store.bin")

    # -- worker 1: dies hard (no cleanup) after K=3 checkpoint appends
    killed = _run_child("""
        from repro.serve import cache_store

        K = 3
        real = cache_store.CacheStore.append
        calls = {"n": 0}

        def dying_append(self, entries):
            r = real(self, entries)
            calls["n"] += 1
            if calls["n"] >= K:
                os._exit(9)         # SIGKILL-style: skip atexit/finally
            return r

        cache_store.CacheStore.append = dying_append
        sweep.eval_sweep(points(), backend="numpy",
                         checkpoint=os.environ["STORE"],
                         checkpoint_every=1)
        os._exit(1)                 # unreachable: the grid must die
    """, store)
    assert killed.returncode == 9, (killed.stdout, killed.stderr)
    assert os.path.exists(store)

    # -- worker 2: restart against the same store; only the tail runs
    resumed = _run_child("""
        recs = sweep.eval_sweep(points(), backend="numpy",
                                checkpoint=os.environ["STORE"],
                                checkpoint_every=1)
        st = sweep.cache_stats()
        print(f"HITS={st['hits']} MISSES={st['misses']}")
        print("DIGEST=" + digest(recs))
    """, store)
    assert resumed.returncode == 0, resumed.stderr
    # cache_hits == points completed before the kill, misses == the rest
    assert "HITS=3 MISSES=3" in resumed.stdout

    # -- reference: uninterrupted run, no store — bitwise-equal records
    reference = _run_child("""
        recs = sweep.eval_sweep(points(), backend="numpy")
        print("DIGEST=" + digest(recs))
    """, store)
    assert reference.returncode == 0, reference.stderr
    dig = [line for line in resumed.stdout.splitlines()
           if line.startswith("DIGEST=")]
    ref = [line for line in reference.stdout.splitlines()
           if line.startswith("DIGEST=")]
    assert dig == ref and dig, (resumed.stdout, reference.stdout)
