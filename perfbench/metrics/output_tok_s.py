"""Served tokens confirmed in the window over the window's wall time."""


def read(ctx):
    w = ctx["window"]
    n = sum(1 for s in w.rec.reqs for t in s.times if w.start <= t < w.end)
    return n / (w.end - w.start)
