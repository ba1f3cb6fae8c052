"""Tokens stamped in the window (a test fixture's per-layer metric)."""


def read(ctx):
    t0, t_end = ctx["window"]
    n = sum(1 for ts in ctx["times"].values() for t in ts if t0 <= t <= t_end)
    return n or None
