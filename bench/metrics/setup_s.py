"""Seconds from process start to the window's opening: imports, building
the system, and warming every executable the traffic reaches (compiles,
or reads from the persistent compile cache)."""


def read(ctx):
    return ctx["setup_s"]
