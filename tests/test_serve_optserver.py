"""Optimization-server invariants (DESIGN.md §14): the solo==served
exactness contract across every (kind × method × engine × congestion)
combination, coalescing, bounded-queue backpressure, bad-request
isolation, retry-with-restore, and the kill/restart chaos test over the
persistent cache store."""
import numpy as np
import pytest

from repro.core import EvalOptions, GemmOp, Task, make_hw
from repro.core import sweep
from repro.core.ga import GAConfig
from repro.core.miqp import MIQPConfig
from repro.core.pipelining import PipelineConfig
from repro.core.workload import uniform_partition
from repro.serve import BadRequest, OptRequest, OptServer, ServerOverloaded
from repro.serve.coalesce import group_requests


def toy_task(n=3, m=512):
    ops = [GemmOp("g0", M=m, K=256, N=512)]
    for i in range(1, n):
        ops.append(GemmOp(f"g{i}", M=m, K=ops[-1].N, N=512, chained=True))
    return Task(f"toy{n}_{m}", ops)


HW = make_hw("A", 2, "hbm")
GA_CFG = GAConfig(generations=4, population=16, patience=4, seed=3)
MIQP_CFG = MIQPConfig(engine="lattice", candidate_budget=256,
                      eval_budget=1024, beam_width=4, refine_sweeps=1,
                      pair_refine=4, descent_sweeps=2, score_chunk=256)
SEGS = [("a", 1.0, 2.0, 1.0), ("b", 0.5, 1.0, 0.5), ("c", 0.2, 0.8, 0.3)]


@pytest.fixture(autouse=True)
def _fresh_cache():
    sweep.clear_cache()
    yield
    sweep.clear_cache()


def _result_equal(a, b):
    if isinstance(a, dict):                      # eval record
        assert a["latency"] == b["latency"]
        assert a["energy"] == b["energy"]
        assert a["edp"] == b["edp"]
        np.testing.assert_array_equal(a["t_in"], b["t_in"])
        np.testing.assert_array_equal(a["t_out"], b["t_out"])
        return
    if hasattr(a, "pipelined"):                  # PipelineResult
        assert (a.batch, a.sequential, a.pipelined) == \
            (b.batch, b.sequential, b.pipelined)
        return
    assert a.objective == b.objective            # GAResult / MIQPResult
    np.testing.assert_array_equal(a.partition.Px, b.partition.Px)
    np.testing.assert_array_equal(a.partition.Py, b.partition.Py)
    np.testing.assert_array_equal(a.redist_mask, b.redist_mask)


# ------------------------------------------------------ solo == served
def _eval_requests(backend):
    task = toy_task()
    reqs = []
    for cong in ("regime", "flow"):
        for redist in (False, True):
            opts = EvalOptions(redistribution=redist, async_exec=True,
                               congestion=cong)
            reqs.append(OptRequest(
                "eval", sweep.EvalPoint(task, HW, opts),
                backend=backend))
    return reqs


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_served_eval_bit_identical_to_solo(backend):
    """N concurrent same-shape eval requests (both congestion modes)
    coalesce into batched calls yet return bit-identical records to solo
    ``eval_sweep`` calls — the solo==served contract."""
    reqs = _eval_requests(backend)
    solo = [sweep.eval_sweep([r.point], backend=backend, cache=False)[0]
            for r in reqs]
    sweep.clear_cache()
    srv = OptServer(autostart=False)
    futs = [srv.submit(r) for r in reqs]
    srv.start()                       # all queued → one worker batch
    recs = [f.result(timeout=120) for f in futs]
    for s, r in zip(solo, recs):
        _result_equal(s, r)
    st = srv.stats()
    assert st["completed"] == len(reqs)
    # one CallKey → ONE coalesced sweep call for all 4 requests
    assert st["batches"] == 1
    assert st["coalesce_factor"] == len(reqs)
    srv.kill()


@pytest.mark.parametrize("method,cfg,backend", [
    ("ga", GA_CFG, "numpy"),
    ("ga", GA_CFG, "jax"),
    ("miqp", MIQP_CFG, "jax"),
])
def test_served_solve_bit_identical_to_solo(method, cfg, backend):
    pts = [sweep.EvalPoint(toy_task(2), HW),
           sweep.EvalPoint(toy_task(2, 256), HW)]
    reqs = [OptRequest("solve", pt, objective="latency", method=method,
                       cfg=cfg, backend=backend) for pt in pts]
    solo = [sweep.solve_grid([pt], "latency", cfg, backend=backend,
                             cache=False, method=method)[0] for pt in pts]
    sweep.clear_cache()
    srv = OptServer(autostart=False)
    futs = [srv.submit(r) for r in reqs]
    srv.start()
    recs = [f.result(timeout=300) for f in futs]
    for s, r in zip(solo, recs):
        _result_equal(s, r)
    srv.kill()


@pytest.mark.parametrize("engine", ["python", "vectorized"])
def test_served_pipeline_bit_identical_to_solo(engine):
    cfg = PipelineConfig(engine=engine)
    pts = [sweep.PipelinePoint(SEGS, b) for b in (2, 4, 8)]
    reqs = [OptRequest("pipeline", pt, cfg=cfg) for pt in pts]
    solo = [sweep.pipeline_sweep([pt], cfg, cache=False)[0] for pt in pts]
    sweep.clear_cache()
    srv = OptServer(autostart=False)
    futs = [srv.submit(r) for r in reqs]
    srv.start()
    recs = [f.result(timeout=120) for f in futs]
    for s, r in zip(solo, recs):
        _result_equal(s, r)
    srv.kill()


def test_mixed_kind_traffic_coalesces_per_call_key():
    """A mixed batch (eval + solve + pipeline) groups into exactly one
    sweep call per CallKey, results all correct."""
    ereqs = _eval_requests("jax")[:2]
    preqs = [OptRequest("pipeline", sweep.PipelinePoint(SEGS, b))
             for b in (2, 4)]
    sreqs = [OptRequest("solve", sweep.EvalPoint(toy_task(2), HW),
                        cfg=GA_CFG, backend="numpy")]
    reqs = ereqs + preqs + sreqs
    assert len(group_requests(reqs)) == 3
    srv = OptServer(autostart=False)
    futs = [srv.submit(r) for r in reqs]
    srv.start()
    for f in futs:
        f.result(timeout=300)
    st = srv.stats()
    assert st["batches"] == 3
    assert st["completed"] == len(reqs)
    assert st["coalesce_factor"] == pytest.approx(len(reqs) / 3)
    srv.kill()


# -------------------------------------------------------- backpressure
def test_bounded_queue_backpressure():
    srv = OptServer(max_queue=3, autostart=False)
    req = lambda: OptRequest("eval", sweep.EvalPoint(toy_task(), HW))
    futs = [srv.submit_nowait(req()) for _ in range(3)]
    with pytest.raises(ServerOverloaded):
        srv.submit_nowait(req())
    with pytest.raises(ServerOverloaded):
        srv.submit(req(), timeout=0.01)
    # Backpressure clears once the worker drains the queue.
    srv.start()
    for f in futs:
        f.result(timeout=120)
    srv.submit(req()).result(timeout=120)
    assert srv.stats()["completed"] == 4
    srv.kill()


# ------------------------------------------------ bad-request isolation
def test_bad_requests_rejected_not_fatal():
    task = toy_task()
    bad_part = uniform_partition(task, HW.X, HW.Y)
    bad_part.Px[0, 0] += 7            # sums no longer match M
    good = OptRequest("eval", sweep.EvalPoint(task, HW))
    bads = [
        OptRequest("eval", sweep.EvalPoint(task, HW,
                                           partition=bad_part)),
        OptRequest("nonsense", sweep.EvalPoint(task, HW)),
        OptRequest("solve", sweep.EvalPoint(task, HW),
                   objective="speed"),
        OptRequest("solve", sweep.EvalPoint(task, HW), method="ga",
                   cfg=MIQP_CFG),
        OptRequest("pipeline", sweep.PipelinePoint(SEGS, 0)),
        OptRequest("pipeline",
                   sweep.PipelinePoint([("a", np.nan, 1.0, 1.0)], 2)),
        OptRequest("eval", sweep.EvalPoint(task, HW), backend="cuda"),
    ]
    ref = sweep.eval_sweep([good.point], cache=False)[0]
    sweep.clear_cache()
    srv = OptServer(autostart=False)
    bad_futs = [srv.submit(b) for b in bads]
    good_fut = srv.submit(good)
    srv.start()
    # Every malformed request errors with BadRequest on its own future…
    for f in bad_futs:
        with pytest.raises(BadRequest):
            f.result(timeout=60)
    # …while the cohort request and the worker survive.
    _result_equal(ref, good_fut.result(timeout=60))
    _result_equal(ref, srv.submit(good).result(timeout=60))
    st = srv.stats()
    assert st["rejected"] == len(bads)
    assert st["completed"] == 2
    srv.kill()


# ---------------------------------------------------- retry-with-restore
def test_transient_failure_retries_then_succeeds():
    srv = OptServer(autostart=False, max_retries=2)
    real = srv._calls["eval"]
    fails = {"n": 2}

    def flaky(*a, **kw):
        if fails["n"] > 0:
            fails["n"] -= 1
            raise RuntimeError("simulated transient engine failure")
        return real(*a, **kw)

    srv._calls["eval"] = flaky
    req = OptRequest("eval", sweep.EvalPoint(toy_task(), HW))
    fut = srv.submit(req)
    srv.start()
    rec = fut.result(timeout=120)
    assert rec["latency"] > 0
    st = srv.stats()
    assert st["retries"] == 2
    assert st["failed"] == 0
    srv.kill()


def test_persistent_failure_isolated_by_solo_fallback():
    """A request that poisons its whole coalesced call must not take the
    cohort down: after retries the group re-runs solo and only the
    guilty request errors."""
    poison = sweep.EvalPoint(toy_task(4), HW)
    ok_pts = [sweep.EvalPoint(toy_task(), HW),
              sweep.EvalPoint(toy_task(3, 256), HW)]
    srv = OptServer(autostart=False, max_retries=1)
    real = srv._calls["eval"]

    def booby_trapped(pts, **kw):
        if any(p is poison for p in pts):
            raise ValueError("simulated poisoned point")
        return real(pts, **kw)

    srv._calls["eval"] = booby_trapped
    futs = [srv.submit(OptRequest("eval", pt))
            for pt in (ok_pts[0], poison, ok_pts[1])]
    srv.start()
    assert futs[0].result(timeout=120)["latency"] > 0
    assert futs[2].result(timeout=120)["latency"] > 0
    with pytest.raises(ValueError):
        futs[1].result(timeout=120)
    st = srv.stats()
    assert st["retries"] == 1
    assert st["solo_fallbacks"] == 1
    assert st["failed"] == 1
    assert st["completed"] == 2
    srv.kill()


# -------------------------------------------------------- chaos / store
def test_chaos_kill_restart_resumes_from_store(tmp_path):
    """Kill a server mid-grid (after half the points completed, without
    graceful shutdown); a restarted server on the same store must serve
    the completed half purely from cache — zero recomputation — and
    return bit-identical results for the full grid."""
    store = tmp_path / "sweep-cache.bin"
    pts = [sweep.EvalPoint(toy_task(3, m), HW)
           for m in (128, 256, 384, 512, 640, 768)]
    ref = [sweep.eval_sweep([p], cache=False)[0] for p in pts]
    sweep.clear_cache()

    srv1 = OptServer(store_path=str(store), flush_every=1)
    futs = [srv1.submit(OptRequest("eval", pt)) for pt in pts[:3]]
    for f in futs:
        f.result(timeout=120)
    srv1.drain(timeout=60)
    srv1.kill()                        # crash: NO graceful close/save

    sweep.clear_cache()                # "new process"
    srv2 = OptServer(store_path=str(store), flush_every=1)
    assert srv2.store_info["loaded"] == 3
    assert not srv2.store_info["cold_start"]
    futs = [srv2.submit(OptRequest("eval", pt)) for pt in pts]
    recs = [f.result(timeout=120) for f in futs]
    st = srv2.stats()
    # completed points came from the store; only the killed-off half
    # was computed
    assert st["cache_hits"] == 3
    assert st["cache_misses"] == 3
    for a, b in zip(ref, recs):
        _result_equal(a, b)
    srv2.close()
    # graceful close full-saves: a third server loads all six
    sweep.clear_cache()
    srv3 = OptServer(store_path=str(store))
    assert srv3.store_info["loaded"] == 6
    srv3.kill()


def test_store_survives_torn_tail(tmp_path):
    """A store torn mid-record (crash mid-append) still resumes the
    intact prefix on restart."""
    store = tmp_path / "sweep-cache.bin"
    pts = [sweep.EvalPoint(toy_task(3, m), HW) for m in (128, 256, 384)]
    srv = OptServer(store_path=str(store), flush_every=1)
    for f in [srv.submit(OptRequest("eval", pt)) for pt in pts]:
        f.result(timeout=120)
    srv.drain(timeout=60)
    srv.kill()
    size = store.stat().st_size
    with open(store, "r+b") as f:
        f.truncate(size - 11)
    sweep.clear_cache()
    srv2 = OptServer(store_path=str(store))
    assert srv2.store_info["torn_tail"]
    assert 0 < srv2.store_info["loaded"] < len(pts)
    srv2.kill()


# -------------------------------------------------------------- stats
def test_stats_shape_and_latency_fields():
    srv = OptServer(autostart=False)
    reqs = _eval_requests("jax")[:2]
    futs = [srv.submit(r) for r in reqs]
    srv.start()
    for f in futs:
        f.result(timeout=120)
    st = srv.stats()
    assert st["submitted"] == 2 and st["inflight"] == 0
    assert st["requests_per_s"] > 0
    assert 0 < st["p50_ms"] <= st["p99_ms"]
    assert st["cache_hit_rate"] == 0.0       # all fresh points
    assert st["store"]["loaded"] == 0        # no store configured
    srv.kill()


def test_cli_demo_runs(monkeypatch, capsys, tmp_path):
    from repro.serve import optserver as mod

    def tiny_traffic(n):
        return [OptRequest("eval", sweep.EvalPoint(toy_task(), HW))
                for _ in range(n)]

    monkeypatch.setattr(mod, "_demo_requests", tiny_traffic)
    # With the variable set, the CLI's compile-cache helper changes no
    # process-wide setting (JAX read the variable at import).
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    mod.main(["--requests", "3",
              "--store", str(tmp_path / "cli-store.bin")])
    out = capsys.readouterr().out
    assert "served 3/3 requests" in out


def test_compile_cache_helper(monkeypatch, tmp_path):
    """The CLIs' cache helper defers to JAX_COMPILATION_CACHE_DIR and
    otherwise points JAX at the checkout's fixed .jax_cache/."""
    import jax

    from repro.runtime.compile_cache import DEFAULT_DIR, use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert use_compile_cache() == str(DEFAULT_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert DEFAULT_DIR.name == ".jax_cache"
        assert (DEFAULT_DIR.parent / "chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
