"""What the per-layer readers (``bench/metrics/*.py``) share.

A reader is ``read(ctx) -> float | None``. ``ctx`` is the traced run's
context: ``trace`` (``trace_reduce.Trace``, device clock), ``window``
(``(t0, t_end)`` on the host's ``perf_counter``), ``host_spans`` (the
program's own spans as ``(start, end, name)`` on that clock), ``chip``
(the peaks of the device), ``config`` (the configuration file), and per
cell kind its records: for serving ``prompts``, ``due``, ``times`` (token
emission stamps) and ``admitted`` (admission times); for training
``step_bounds`` (when the measured window opened, then when each step
ended), ``tokens_per_step`` and ``chips``. ``window`` and the trace cover
the profiled part of the measured window. A reader that finds nothing to
read returns None.
"""
from __future__ import annotations

import numpy as np

import counts


def in_window(ctx, t) -> bool:
    t0, t_end = ctx["window"]
    return t0 <= t <= t_end


def span_list(ctx, name: str) -> list:
    """The program's ``name`` spans that lie wholly in the window, as
    ``(start, end)``, not merged."""
    t0, t_end = ctx["window"]
    return [(s, e) for s, e, n in ctx["host_spans"]
            if n == name and s >= t0 and e <= t_end]


def spans(ctx, name: str) -> list:
    """The program's ``name`` spans, clipped to the window, merged."""
    t0, t_end = ctx["window"]
    out = []
    for s, e, n in sorted(ctx["host_spans"]):
        if n != name or e <= t0 or s >= t_end:
            continue
        s, e = max(s, t0), min(e, t_end)
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def decode_steps(ctx):
    """``(request, position)`` of every token a decode step emitted in the
    window: a request's first token comes from its prefill, its ``j``-th
    after that from the decode step fed position ``len(prompt) + j - 1``."""
    for uid, ts in ctx["times"].items():
        n = len(ctx["prompts"][uid])
        for j, t in enumerate(ts[1:], start=1):
            if in_window(ctx, t):
                yield uid, n + j - 1


def steps_in_window(ctx) -> float:
    """Training steps done in the window; a step cut by one of its edges
    counts by the share of it that lies inside."""
    t0, t_end = ctx["window"]
    b = ctx["step_bounds"]
    return sum(max(0.0, min(e, t_end) - max(s, t0)) / (e - s)
               for s, e in zip(b, b[1:]) if e > s)


def prefills(ctx) -> list:
    """Prompt lengths of the requests whose prefill ended in the window
    (the first token is emitted as it ends)."""
    return [len(ctx["prompts"][u]) for u, ts in ctx["times"].items()
            if ts and in_window(ctx, ts[0])]


def model(ctx) -> dict:
    return ctx["config"]["model"]


head_dim = counts.head_dim


def mfu(ctx, flops: float, seconds: float, chips: int = 1):
    if seconds <= 0 or flops <= 0:
        return None
    return 100.0 * flops / seconds / (chips * ctx["chip"]["flops_per_s"])


def percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q)) \
        if len(values) else None


__all__ = ["counts", "decode_steps", "head_dim", "in_window", "mfu",
           "model", "percentile", "prefills", "span_list", "spans",
           "steps_in_window"]
