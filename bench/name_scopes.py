"""Device time of the operations under one of the program's
``jax.named_scope`` names, read from a trace's ``tf_op`` (the JAX name
stack of each device operation)."""
from __future__ import annotations

import re

import numpy as np

import trace_reduce


def holds(scope: str):
    """A test of whether a ``tf_op`` holds ``scope`` as one name of its
    stack, itself or under a transformation: ``.../ss_core/...``,
    ``jvp(ss_core)``, ``transpose(jvp(ss_core))``; never a longer name
    that begins with it."""
    pattern = re.compile(rf"(?:^|[/(]){re.escape(scope)}(?:[/):]|$)")
    return lambda tf_op: bool(pattern.search(tf_op))


def scope_seconds(trace, scope: str) -> float:
    """Seconds of the window, averaged over the devices, in which an
    operation under ``scope`` runs: the union of their intervals, so a
    loop and the operations of its body count once."""
    under = holds(scope)
    per = []
    for ops in trace.devices.values():
        sel = [op for op in ops if len(op) > 4 and under(op[4])]
        busy = trace_reduce.clip(trace_reduce.union(sel), trace.lo, trace.hi)
        per.append(sum(e - s for s, e in busy))
    return float(np.mean(per)) / 1e9
