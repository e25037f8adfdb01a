"""The control and the planted faults that the checks must catch.

Each is a context manager that breaks the timed path underneath the
harness, inside the program's own engine modules, and restores it on
exit. None of them is used by a benchmark run: the control runs on the
chip from ``bench/tools/readings.py --mode control``, and
``bench/tests`` runs all of them at a small size.

* ``float32`` -- the control: every engine runs outside its float64
  scope, in float32, the precision below the one the configurations
  state.
* ``state_unchanged`` -- each GA chunk returns the state it was given.
* ``no_search`` -- each GA chunk runs every generation, but its
  offspring are copies of their parents: no crossover, no mutation.
* ``half_batch`` -- only the first half of each batched call is
  computed; the other half is answered with copies of it.
* ``altered_answer`` -- every answer of each batched call is changed by
  a relative 1e-6 where it is produced (every one, so that a check on a
  sample of the answers sees it).
"""
from __future__ import annotations

import contextlib

import numpy as np

ENGINES = ("evaluator_jax", "ga_jax", "netsim_jax", "pipelining_jax",
           "cosearch")


@contextlib.contextmanager
def _patched(pairs):
    saved = [(obj, name, getattr(obj, name)) for obj, name, _ in pairs]
    try:
        for obj, name, value in pairs:
            setattr(obj, name, value)
        yield
    finally:
        for obj, name, value in saved:
            setattr(obj, name, value)


def _modules():
    import importlib

    return {m: importlib.import_module(f"repro.core.{m}") for m in ENGINES}


def float32():
    mods = _modules()
    return _patched([(mods[m], "x64", contextlib.nullcontext)
                     for m in ENGINES])


def state_unchanged():
    ga = _modules()["ga_jax"]
    orig = ga._chunk_fn

    def chunk_fn(*statics):
        fn = orig(*statics)

        def frozen(consts, win, hp, carry, keys):
            return carry, fn(consts, win, hp, carry, keys)[1]
        return frozen
    return _patched([(ga, "_chunk_fn", chunk_fn)])


def no_search():
    ga = _modules()["ga_jax"]
    orig = ga._chunk_fn
    still = ("p_crossover", "p_mutate_partition", "p_mutate_collector",
             "p_mutate_redist")

    def chunk_fn(*statics):
        fn = orig(*statics)

        def copies(consts, win, hp, carry, keys):
            return fn(consts, win, {**hp, **{k: 0.0 for k in still}},
                      carry, keys)
        return copies
    return _patched([(ga, "_chunk_fn", chunk_fn)])


def _half(n: int) -> np.ndarray:
    """Row ``i`` answered by row ``i mod ceil(n/2)``."""
    return np.arange(n) % max(1, (n + 1) // 2)


def half_batch():
    mods = _modules()
    ev, ga = mods["evaluator_jax"], mods["ga_jax"]
    grid, islands = ev.grid_evaluate, ga.solve_islands

    def grid_evaluate(consts, opts, Px, Py, co, rd, devices="single"):
        k = _half(len(Px))[:(len(Px) + 1) // 2]
        out = grid({a: v[k] for a, v in consts.items()}, opts, Px[k], Py[k],
                   co[k], rd[k], devices=devices)
        return {a: v[_half(len(Px))] for a, v in out.items()}

    def solve_islands(tasks, hws, options, objective, cfg, **kw):
        m = (len(tasks) + 1) // 2
        out = islands(tasks[:m], hws[:m], options, objective, cfg, **kw)
        return [out[i] for i in _half(len(tasks))]
    return _patched([(ev, "grid_evaluate", grid_evaluate),
                     (ga, "solve_islands", solve_islands)])


def altered_answer():
    mods = _modules()
    ev, ga = mods["evaluator_jax"], mods["ga_jax"]
    grid, islands = ev.grid_evaluate, ga.solve_islands

    def grid_evaluate(*a, **k):
        out = dict(grid(*a, **k))
        out["latency"] = np.asarray(out["latency"]) * (1 + 1e-6)
        return out

    def solve_islands(*a, **k):
        out = islands(*a, **k)
        for r in out:
            r.objective *= 1 + 1e-6
        return out
    return _patched([(ev, "grid_evaluate", grid_evaluate),
                     (ga, "solve_islands", solve_islands)])


FAULTS = {"state_unchanged": state_unchanged, "no_search": no_search,
          "half_batch": half_batch, "altered_answer": altered_answer}
