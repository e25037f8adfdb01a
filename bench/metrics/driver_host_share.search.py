"""Share of the traced window, in percent, in which the harness is inside
a sweep call and the device is idle: host work of the sweep driver
(grouping, evaluator constants, record copies) that the device waits on."""
from bench.harness import trace


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"]:
        return None
    return 100.0 * trace.idle_under(tr, "sweep_call") / trace.window_ns(tr)
