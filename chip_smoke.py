"""Smoke run of the MCMComm solver stack on a TPU.

    python chip_smoke.py               # one chip: every engine
    python chip_smoke.py --four-chips  # the sharded sweep fabric, 4 chips

One process drives the system's own entry points (``repro.core.sweep``
and the coalescing ``OptServer``) with ``backend="jax"``, on an empty
sweep cache and with no store, at the published sizes: ViT-B/16 (depth
12, d 768, 12 heads, 197 tokens) and AlexNet on the 16x16 package of
Fig. 9/10 (``make_hw("A", 16, "hbm")``) and on the 4x4 package. Every
answer is checked against something independent of the engine that made
it:

* evaluation records against the numpy reference evaluator at the
  DESIGN.md §8 tolerance (rtol 1e-9);
* each solver's best genome re-scored by the numpy evaluator (the
  co-search genome also by the serial list scheduler);
* the vectorized SGS makespans against the serial scheduler (bitwise
  on the CPU; at rtol 1e-9 on a TPU, whose float64 is emulated and not
  correctly rounded — the count of bitwise-equal ones is printed);
* every served result bitwise against its solo call, and the server's
  cache-miss count above zero (the engines ran, the cache did not
  answer).

Each phase calls its entry point twice on a cleared sweep cache: the
first call pays the compiles (``compile_s`` is the trace + lower +
compile time JAX records), the second gives ``steady_s`` and must equal
the first bitwise. The checks run after both calls, outside the timings.
A failed check raises; nothing is caught. Without a TPU the script exits
nonzero before any phase. The last line of standard output is one JSON
object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: DESIGN.md §8: the jax engines agree with the numpy references to
#: float64 round-off.
RTOL = 1e-9

#: JAX's compile-time events: tracing, lowering, backend compile (the
#: last also covers a persistent-cache read).
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A check of the smoke run did not hold."""


def check(ok, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What each phase runs. The defaults are the published sizes."""

    grids: tuple = (16, 4)
    workloads: tuple = ("vit", "alexnet")
    eval_partitions: int = 3
    ga_population: int = 1024
    ga_generations: int = 6
    miqp_budget: int = 4096
    pipeline_batches: tuple = (4, 16)
    cosearch_population: int = 64
    cosearch_generations: int = 4
    served: int = 32
    sharded_points: int = 8


class CompileClock:
    """Sums the compile-time events JAX reports, from any thread."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
            if event == _COMPILE_EVENTS[-1]:
                self.compiles += 1


def same(a, b) -> bool:
    """Bitwise equality of records: dicts, lists, dataclasses, arrays."""
    if dataclasses.is_dataclass(a) and not isinstance(a, type):
        return type(a) is type(b) and all(
            same(getattr(a, f.name), getattr(b, f.name))
            for f in dataclasses.fields(a))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(same(a[k], b[k]) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(same(x, y) for x, y in zip(a, b))
    if isinstance(a, (np.ndarray, np.generic)) or isinstance(
            b, (np.ndarray, np.generic)):
        a, b = np.asarray(a), np.asarray(b)
        return a.shape == b.shape and a.dtype == b.dtype and (
            a.tobytes() == b.tobytes())
    return a == b


def rel_err(got, want) -> float:
    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    check(np.isfinite(got).all(), "non-finite engine output")
    den = np.maximum(np.abs(want), np.finfo(np.float64).tiny)
    return float(np.max(np.abs(got - want) / den, initial=0.0))


class Smoke:
    """The phases. ``sizes`` fixes what each one runs."""

    def __init__(self, sizes: Sizes):
        from repro.core import EvalOptions, make_hw, sweep
        from repro.graphs import WORKLOADS

        self.sizes = sizes
        self.sweep = sweep
        self.make_hw = make_hw
        self.clock = CompileClock()
        self.tasks = [WORKLOADS[w](batch=1) for w in sizes.workloads]
        self.hws = [make_hw("A", g, "hbm") for g in sizes.grids]
        self.opts = EvalOptions(redistribution=True, async_exec=True)
        self.rng = np.random.default_rng(0)
        self.timings: dict[str, dict] = {}

    # ------------------------------------------------------------ timing
    def phase(self, name: str, call, verify, answers=lambda out: out):
        """Time ``call()`` cold and warm on a cleared sweep cache, check
        that both gave the same ``answers``, then ``verify`` the cold
        result; returns it."""
        clk = self.clock
        runs = []
        for _ in range(2):
            self.sweep.clear_cache()
            s0, c0 = clk.seconds, clk.compiles
            t0 = time.perf_counter()
            out = call()
            runs.append((out, time.perf_counter() - t0,
                         clk.seconds - s0, clk.compiles - c0))
        (cold, cold_s, compile_s, compiles), (warm, steady_s, _, recomp) \
            = runs
        check(same(answers(cold), answers(warm)),
              f"{name}: the warm call differs from the cold one")
        summary = verify(cold)
        self.timings[name] = {"compile_s": compile_s, "compiles": compiles,
                              "cold_s": cold_s, "steady_s": steady_s,
                              "steady_compiles": recomp}
        print(f"phase {name}: compile_s={compile_s:.3f} ({compiles} "
              f"compiles) cold_s={cold_s:.3f} steady_s={steady_s:.3f} "
              f"(steady compiles {recomp}) | {summary}", flush=True)
        return cold

    def points(self, options, partitions: int, tasks=None, hws=None):
        """LS-uniform points for every (workload, package), each followed
        by ``partitions`` random in-domain partitions of it."""
        from repro.core.sweep import EvalPoint
        from repro.core.workload import (clamp_partition_to_domain,
                                         uniform_partition)

        pts = []
        for task in tasks or self.tasks:
            for hw in hws or self.hws:
                pts.append(EvalPoint(task, hw, options))
                for _ in range(partitions):
                    p = uniform_partition(task, hw.X, hw.Y)
                    p.Px = np.maximum(
                        p.Px + self.rng.integers(-2, 3, p.Px.shape) * hw.R,
                        0)
                    p = clamp_partition_to_domain(p, task, hw.X, hw.Y,
                                                  hw.R, hw.C)
                    p.collectors = self.rng.integers(0, hw.Y, len(task))
                    pts.append(EvalPoint(task, hw, options, p))
        return pts

    def solver_points(self):
        """ViT-B/16 on the 16x16 package and AlexNet on the 4x4 one,
        each on the plain and the diagonal-link mesh (one shape group
        per workload). The other two pairings are evaluated only: each
        solver group costs a compile per engine."""
        from repro.core.sweep import EvalPoint

        return [EvalPoint(t, dataclasses.replace(hw, diagonal_links=d),
                          self.opts)
                for t, hw in zip(self.tasks, self.hws) for d in (False, True)]

    # ------------------------------------------------------------ checks
    def check_eval(self, pts, recs) -> str:
        """Records against the numpy reference evaluator."""
        ref = self.sweep.eval_sweep(pts, backend="numpy", cache=False)
        worst = 0.0
        for r, w in zip(recs, ref):
            for k in ("latency", "energy", "edp", "t_in", "t_comp", "t_out",
                      "E_sram", "E_mac", "E_mem", "E_nop"):
                worst = max(worst, rel_err(r[k], w[k]))
        check(worst <= RTOL, f"evaluator parity: max rel err {worst:.3e} "
                             f"> rtol {RTOL}")
        return f"{len(recs)} records vs numpy, max rel err {worst:.3e}"

    def rescore(self, pt, part, redist, diagonal=None):
        """The numpy evaluator's verdict on one genome."""
        from repro.core import Evaluator

        hw = pt.hw if diagonal is None else dataclasses.replace(
            pt.hw, diagonal_links=diagonal)
        part.validate(pt.task)
        return Evaluator(pt.task, hw, pt.options,
                         backend="numpy").evaluate(part, redist)

    def check_rescored(self, what, pts, res, objective) -> str:
        worst = 0.0
        for pt, r in zip(pts, res):
            ev = self.rescore(pt, r.partition, r.redist_mask)
            worst = max(worst, rel_err(r.objective, getattr(ev, objective)))
        check(worst <= RTOL, f"{what} re-score: rel err {worst:.3e}")
        return (f"{len(res)} solves; best genomes re-scored by numpy, max "
                f"rel err {worst:.3e}")

    # ------------------------------------------------------------ phases
    def eval_phase(self, options):
        pts = self.points(options, self.sizes.eval_partitions)
        recs = self.phase(
            f"eval_sweep[{options.congestion}]",
            lambda: self.sweep.eval_sweep(pts, backend="jax"),
            lambda recs: self.check_eval(pts, recs))
        return pts, recs

    def ga_cfg(self, seed: int = 0):
        from repro.core import GAConfig

        s = self.sizes
        return GAConfig(population=s.ga_population,
                        generations=s.ga_generations,
                        patience=s.ga_generations, seed=seed,
                        backend="jax", engine="vectorized")

    def ga_phase(self):
        pts = self.solver_points()
        cfg = self.ga_cfg()

        def verify(res):
            for r in res:
                check(np.all(np.diff(r.history) <= 0),
                      "GA best-so-far history is not monotone")
                check(len(r.history) * cfg.population == r.evaluations,
                      "GA evaluation count")
            return (f"P={cfg.population}, {cfg.generations} generations: "
                    + self.check_rescored("GA", pts, res, "edp"))
        self.phase("solve_grid[ga]",
                   lambda: self.sweep.solve_grid(pts, "edp", cfg,
                                                 backend="jax", method="ga"),
                   verify)

    def miqp_cfg(self):
        from repro.core import MIQPConfig

        b = self.sizes.miqp_budget
        return MIQPConfig(engine="lattice", backend="jax",
                          candidate_budget=b, eval_budget=b,
                          descent_sweeps=1, refine_sweeps=1, pair_refine=0)

    def miqp_phase(self):
        pts = self.solver_points()[::2]
        cfg = self.miqp_cfg()

        def verify(res):
            check(all(r.engine == "lattice" for r in res), "MIQP engine")
            return self.check_rescored("MIQP", pts, res, "latency")
        self.phase("solve_grid[miqp]",
                   lambda: self.sweep.solve_grid(pts, "latency", cfg,
                                                 backend="jax",
                                                 method="miqp"),
                   verify)

    def pipeline_phase(self, eval_recs):
        """Each evaluated schedule's per-op phases, pipelined."""
        from repro.core.pipelining import PipelineConfig, pipeline_batch
        from repro.core.sweep import PipelinePoint

        pts = []
        for rec in eval_recs:
            segs = [(f"op{i}", float(a), float(b), float(c))
                    for i, (a, b, c) in enumerate(
                        zip(rec["t_in"], rec["t_comp"], rec["t_out"]))]
            pts += [PipelinePoint(segs, B)
                    for B in self.sizes.pipeline_batches]
        cfg = PipelineConfig(engine="vectorized", backend="jax")

        def verify(res):
            # Bitwise on the CPU (DESIGN.md §13). A TPU's float64 is
            # emulated and not correctly rounded, so there the schedule's
            # additions agree with the serial engine's only to rtol.
            worst, exact = 0.0, 0
            for pt, r in zip(pts, res):
                serial = pipeline_batch(
                    pt.segments, pt.batch,
                    config=PipelineConfig(engine="python")).pipelined
                exact += r.pipelined == serial
                worst = max(worst, rel_err(r.pipelined, serial))
            check(worst <= RTOL, f"SGS makespan vs serial: rel err "
                                 f"{worst:.3e}")
            return (f"{len(res)} makespans vs the serial SGS: {exact} "
                    f"bitwise equal, max rel err {worst:.3e}")
        self.phase("pipeline_sweep",
                   lambda: self.sweep.pipeline_sweep(pts, cfg,
                                                     backend="jax"),
                   verify)

    def cosearch_cfg(self, seed: int = 0):
        from repro.core import CoSearchConfig

        s = self.sizes
        return CoSearchConfig(population=s.cosearch_population,
                              generations=s.cosearch_generations,
                              patience=s.cosearch_generations, seed=seed)

    def cosearch_rescore(self, pt, r, batch: int) -> dict:
        """The fused objective recomputed on the host: numpy evaluator,
        the segment merge, the serial SGS."""
        from repro.core.pipelining import PipelineConfig, pipeline_batch

        ev = self.rescore(pt, r.partition, r.redist_mask, r.diagonal)
        n = len(pt.task)
        seg_id = np.concatenate([[0], np.cumsum(r.seg_mask[:-1])])
        slots = np.zeros((n, 3))
        np.add.at(slots, seg_id,
                  np.stack([ev.t_in, ev.t_comp, ev.t_out], axis=1))
        segs = [(f"s{i}", *map(float, slots[i])) for i in range(n)]
        lat = pipeline_batch(segs, batch, config=PipelineConfig(
            engine="python")).pipelined / batch
        return {"edp": ev.energy * lat, "latency": lat, "energy": ev.energy}

    def cosearch_phase(self):
        pts = self.solver_points()[::2]
        cfg = self.cosearch_cfg()

        def verify(res):
            worst = 0.0
            for pt, r in zip(pts, res):
                want = self.cosearch_rescore(pt, r, cfg.batch)
                for k in ("edp", "latency", "energy"):
                    worst = max(worst, rel_err(getattr(r, k), want[k]))
            check(worst <= RTOL, f"co-search re-score: rel err "
                                 f"{worst:.3e}")
            return (f"{len(res)} joint searches; best genomes re-scored by "
                    f"numpy + serial SGS, max rel err {worst:.3e}")
        self.phase("solve_grid[cosearch]",
                   lambda: self.sweep.solve_grid(pts, "edp", cfg,
                                                 backend="jax",
                                                 method="cosearch"),
                   verify)

    def requests(self):
        """Mixed traffic on shapes the earlier phases compiled, with new
        partitions, seeds, objectives and durations: every request
        misses the cache."""
        from repro.core.sweep import PipelinePoint
        from repro.serve.coalesce import OptRequest

        s = self.sizes
        small = self.solver_points()[-2:]        # AlexNet, 4x4
        reqs = [OptRequest("eval", p, backend="jax")
                for p in self.points(self.opts, 1) if p.partition is not None]
        reqs += [OptRequest("solve", p, "edp", "ga", self.ga_cfg(seed=1),
                            backend="jax") for p in small]
        reqs += [OptRequest("solve", p, "edp", "miqp", self.miqp_cfg(),
                            backend="jax") for p in small]
        reqs += [OptRequest("solve", small[0], "latency", "cosearch",
                            self.cosearch_cfg(seed=1), backend="jax")]
        lengths = sorted({len(t) for t in self.tasks})
        while len(reqs) < s.served:
            n = lengths[len(reqs) % len(lengths)]
            segs = [(f"op{i}", *map(float, self.rng.uniform(0.1, 2.0, 3)))
                    for i in range(n)]
            reqs.append(OptRequest(
                "pipeline", PipelinePoint(segs, s.pipeline_batches[0]),
                backend="jax"))
        return reqs

    def solo(self, req):
        sw = self.sweep
        if req.kind == "eval":
            return sw.eval_sweep([req.point], backend="jax", cache=False)[0]
        if req.kind == "pipeline":
            return sw.pipeline_sweep([req.point], req.cfg, backend="jax",
                                     cache=False)[0]
        return sw.solve_grid([req.point], req.objective, req.cfg,
                             backend="jax", cache=False,
                             method=req.method)[0]

    def serve_phase(self):
        from repro.serve.optserver import OptServer

        reqs = self.requests()

        def call():
            srv = OptServer(store_path=None)
            try:
                futs = [srv.submit(r) for r in reqs]
                served = [f.result(timeout=900) for f in futs]
                st = srv.stats()
            finally:
                srv.close()
            return served, {k: st[k] for k in ("completed", "failed",
                                               "batches", "cache_misses")}

        def verify(out):
            served, st = out
            check(st["completed"] == len(reqs) and st["failed"] == 0,
                  f"server completed {st['completed']}/{len(reqs)}")
            check(st["cache_misses"] > 0, "every request was a cache hit")
            for r, got in zip(reqs, served):
                check(same(got, self.solo(r)),
                      f"served {r.kind}/{r.method} differs from its solo "
                      f"call")
            return (f"{len(reqs)} requests in {st['batches']} coalesced "
                    f"calls, cache_misses={st['cache_misses']}, each "
                    f"bitwise equal to its solo call")
        self.phase("optserver", call, verify, answers=lambda out: out[0])

    def run_one_chip(self):
        pts, recs = self.eval_phase(self.opts)
        self.eval_phase(dataclasses.replace(self.opts, congestion="flow"))
        self.ga_phase()
        self.miqp_phase()
        self.pipeline_phase([r for p, r in zip(pts, recs)
                             if p.partition is None])
        self.cosearch_phase()
        self.serve_phase()

    # ------------------------------------------------------- four chips
    def run_four_chips(self):
        """The sharded sweep fabric (DESIGN.md §15) against the
        single-device path, bitwise, at ViT-B/16 on the 16x16 package."""
        import jax

        from repro.core import Evaluator, evaluator_jax, sweep_shard
        from repro.core.sweep import EvalPoint, _genome
        from repro.core.x64 import x64

        n_dev = len(jax.devices())
        check(n_dev == 4, f"--four-chips needs 4 devices, found {n_dev}")
        task, hw = self.tasks[0], self.hws[0]
        pts = self.points(self.opts, self.sizes.sharded_points - 1,
                          [task], [hw])
        ga_pts = [EvalPoint(task, self.make_hw("A", hw.X, m,
                                               diagonal_links=d), self.opts)
                  for m in ("hbm", "dram") for d in (False, True)]
        cfg = self.ga_cfg()

        def call(mode):
            return (self.sweep.eval_sweep(pts, backend="jax", cache=False,
                                          devices=mode),
                    self.sweep.solve_grid(ga_pts, "edp", cfg, backend="jax",
                                          cache=False, method="ga",
                                          devices=mode))

        def spans() -> int:
            """Devices holding the sharded program's own output."""
            evs = [Evaluator(p.task, p.hw, p.options, backend="jax")
                   for p in pts]
            consts = [e.consts() for e in evs]
            gen = [_genome(p, e) for p, e in zip(pts, evs)]
            args = ({k: np.stack([c[k] for c in consts])
                     for k in consts[0]},) + tuple(
                np.stack([g[i] for g in gen])[:, None] for i in range(4))
            inner = evaluator_jax._grid_inner(
                *evaluator_jax._static_key(self.opts))
            with x64():
                out = sweep_shard.sharded_grid_call(inner, args, (True,) * 5,
                                                    len(pts))
                return len(out["latency"].sharding.device_set)

        single = self.phase("single_device", lambda: call("single"),
                            lambda out: self.check_eval(pts, out[0]))

        def verify(sharded):
            check(same(single, sharded),
                  "sharded sweeps differ from the single-device sweeps")
            n = spans()
            check(n == 4, f"sharded output spans {n} devices, not 4")
            return (f"eval_sweep ({len(pts)} points) + GA ({len(ga_pts)} "
                    f"islands, P={cfg.population}) bitwise equal to "
                    f"single-device; sharded output spans {n} devices")
        self.phase("sharded", lambda: call("sharded"), verify)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded sweep fabric on 4 chips, "
                         "against the single-device path")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this smoke run needs one", file=sys.stderr)
        return 2
    from repro.runtime.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"device: {dev.device_kind} x{len(jax.devices())} (platform "
          f"{dev.platform}); compile cache {cache_dir}", flush=True)
    smoke = Smoke(Sizes())
    t0 = time.perf_counter()
    if args.four_chips:
        smoke.run_four_chips()
    else:
        smoke.run_one_chip()
    print(f"total_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
