"""Share of the traced window, in percent, in which no operation ran on
the device (averaged over the chips used)."""
from bench.harness import trace


def read(ctx):
    tr = ctx["trace"]
    if not tr["devices"]:
        return None
    return 100.0 * trace.idle_share(tr)
