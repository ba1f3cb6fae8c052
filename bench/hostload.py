"""The host's load over a window, read from ``/proc``: what share of the
machine's CPU time the hypervisor gave to other guests (steal), how busy
this machine's cores were, and how often this process was switched out
against its will. Logged beside a run's numbers to explain its spread;
never a metric. Where ``/proc`` cannot be read, every reading is None."""
from __future__ import annotations


def _cpu() -> list[int] | None:
    try:
        with open("/proc/stat") as fh:
            return [int(x) for x in fh.readline().split()[1:]]
    except (OSError, ValueError):
        return None


def _switches() -> int | None:
    try:
        with open("/proc/self/status") as fh:
            for line in fh:
                if line.startswith("nonvoluntary_ctxt_switches"):
                    return int(line.split()[1])
    except (OSError, ValueError):
        pass
    return None


def snapshot() -> tuple:
    return _cpu(), _switches()


def since(before: tuple) -> dict:
    """Readings between ``before`` (a ``snapshot()``) and now."""
    (c0, s0), (c1, s1) = before, snapshot()
    out = {"steal_share": None, "busy_share": None,
           "involuntary_switches": None}
    d = [b - a for a, b in zip(c0, c1)] if c0 and c1 else []
    total = sum(d[:8])  # user .. steal; guest time is counted in user
    if len(d) > 7 and total > 0:
        out["steal_share"] = d[7] / total
        out["busy_share"] = 1 - (d[3] + d[4]) / total
    if s0 is not None and s1 is not None:
        out["involuntary_switches"] = s1 - s0
    return out
