"""Design points scored per second of host wall time: all the designs
the window's calls asked for, counted from the traffic file's budget,
over all of the window's time."""


def read(ctx):
    w = ctx["window"]
    return w.designs / w.seconds if w.seconds > 0 else None
