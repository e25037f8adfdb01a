"""Closed loop: the next call goes when the last has returned. The window
opens at a call boundary after warm-up and closes with the first call
that finishes after ``seconds``; every call in it is whole."""
from __future__ import annotations

import time
import traceback

from bench.harness.drive import Window
from bench.harness.trace import span


def drive(system, kind, traffic, cfg, seed, seconds, clock, on_open,
          on_close) -> Window:
    for c in kind.warm_calls(traffic, cfg, seed):
        kind.run(system, c, kind.prepare(system, c), cache=False)
    system.sweep.clear_cache()
    w = Window()
    c0 = clock.compiles
    on_open()
    w.t0 = time.perf_counter()
    i = 0
    while time.perf_counter() - w.t0 < seconds:
        with span("generate"):
            c = kind.call(traffic, cfg, seed, i)
            args = kind.prepare(system, c)
        w.attempted += 1
        i += 1
        try:
            with span("sweep_call"):
                out = kind.run(system, c, args)
        except Exception:   # a call that raises is a failed call
            traceback.print_exc()
            w.failed += 1
            continue
        w.designs += c["designs"]
        w.answers.append({"call": c, "out": out})
    w.t1 = time.perf_counter()
    on_close()
    w.compiles = clock.compiles - c0
    return w
