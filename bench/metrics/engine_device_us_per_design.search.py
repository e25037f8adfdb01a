"""Device time of the engine executables in the traced window, in
microseconds per design point completed in it."""
from bench.harness import trace


def read(ctx):
    w, tr = ctx["window"], ctx["trace"]
    if not w.designs or not tr["devices"]:
        return None
    return trace.module_ns(tr) / 1e3 / w.designs
