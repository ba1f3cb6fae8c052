"""From a profiler trace to device busy time, kernel time and idle gaps.

A traced run profiles part of its measured window (``Profile``): it opens
``LEAD_S`` seconds into the window, once the load is steady, and lasts
``TRACE_S`` seconds. ``jax.profiler`` writes a ``.trace.json.gz`` beside
its ``.xplane.pb``; ``extract`` reads the JSON, because only it carries
each device operation's ``source`` and ``tf_op``. From it:

* device operations: the ``XLA Ops`` line of each ``/device:TPU:<n>``
  process. A Pallas kernel is a ``tpu_custom_call``; it is named by the
  source line of its ``pallas_call`` (``repro/kernels/<module>.py:<line>``),
  which stays the same whatever XLA names the instruction.
* host spans: every span of the host thread that holds the benchmark's
  ``bench_window`` mark, the program's own among them (``serve_tick``,
  ``prefill``, ``sample_emit``, ``train_step``, ... written through
  ``jax.profiler.TraceAnnotation`` when its telemetry annotates).

Both are on one clock. Everything past ``extract`` works on plain lists,
so the reduction is checked on a recorded fixture:

* busy time: the union of the intervals in which an operation runs,
  clipped to the window, averaged over the devices; idle share is one
  minus busy over the window;
* kernel time: the summed durations of the kernels whose source lies in
  given modules;
* the breakdown: device operations by self time (a ``while`` or ``call``
  less the operations it holds), labelled by kernel source or ``tf_op``;
  idle stretches labelled by the innermost host span open at their middle
  (``idle`` where none is).
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict

import numpy as np

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
LEAD_S = 5.0
TRACE_S = 8.0
TOP = 10
_SPAN_NAME = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*$")
_NUM_SUFFIX = re.compile(r"[._]\d+$")


# --------------------------------------------------------------------------
# reading a trace file
# --------------------------------------------------------------------------
def kernel_source(args: dict) -> str:
    """``repro/kernels/<module>.py:<line>`` of a Pallas kernel's event
    (from ``src/`` on), or ``""`` for any other operation."""
    if "tpu_custom_call" not in args.get("long_name", ""):
        return ""
    src = args.get("source", "")
    cut = src.rfind("repro/")
    return src[cut:] if cut >= 0 else src


def extract(path: str) -> dict:
    """``{"devices": {process: [[start_ns, end_ns, name, kernel, tf_op],
    ...]}, "spans": [[start_ns, end_ns, name], ...]}`` from a
    ``.trace.json.gz``."""
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("ph") == "M" and e.get("name") == "thread_name":
            threads[(e["pid"], e.get("tid"))] = e["args"]["name"]
    devices = {p: [] for p in procs.values() if p.startswith("/device:TPU:")}
    host = defaultdict(list)
    for e in events:
        if e.get("ph") != "X" or "ts" not in e:
            continue
        proc = procs.get(e["pid"], "")
        s = int(round(e["ts"] * 1e3))
        t = s + int(round(e.get("dur", 0.0) * 1e3))
        if proc in devices:
            if threads.get((e["pid"], e.get("tid"))) == OPS_LINE:
                a = e.get("args", {})
                devices[proc].append([s, t, e["name"], kernel_source(a),
                                      a.get("tf_op", "")])
        elif proc.startswith("/host:") and _SPAN_NAME.match(e["name"]):
            host[(e["pid"], e.get("tid"))].append([s, t, e["name"]])
    marked = [k for k, v in host.items() if any(n == WINDOW for *_, n in v)]
    spans = [sp for k in marked for sp in host[k]]
    return {"devices": devices, "spans": spans}


class Profile:
    """``jax.profiler`` over part of a measured window: ``poll`` it between
    steps; it starts ``lead`` seconds after ``open`` and stops ``length``
    seconds later, marking what it covers with a ``bench_window`` span.
    ``window`` is that stretch on ``time.perf_counter``."""

    def __init__(self, seconds: float):
        self.lead = min(LEAD_S, seconds / 4)
        self.length = min(TRACE_S, seconds - self.lead)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.window = None
        self._start_at = None
        self._mark = None

    def open(self, t0: float) -> None:
        self._start_at = t0 + self.lead

    def poll(self) -> None:
        import jax

        now = time.perf_counter()
        if self._mark is None and self.window is None \
                and now >= self._start_at:
            jax.profiler.start_trace(self.dir)
            self._mark = jax.profiler.TraceAnnotation(WINDOW)
            self._mark.__enter__()
            self._t_a = time.perf_counter()
        elif self._mark is not None and now >= self._t_a + self.length:
            self.stop()

    def stop(self) -> None:
        import jax

        if self._mark is None:
            return
        self.window = (self._t_a, time.perf_counter())
        self._mark.__exit__(None, None, None)
        self._mark = None
        jax.profiler.stop_trace()

    def reduce(self) -> "Trace":
        self.stop()
        try:
            if self.window is None:
                raise RuntimeError("the window closed before the profile "
                                   "opened")
            files = sorted(glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.trace.json.gz")))
            if not files:
                raise RuntimeError("the profiler wrote no trace")
            return Trace(extract(files[-1]))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# interval arithmetic
# --------------------------------------------------------------------------
def union(intervals) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    out = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e, *_ in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def clip_ops(ops, lo, hi) -> list:
    """Operations cut to ``[lo, hi]``, with everything past their bounds
    kept."""
    return [[max(op[0], lo), min(op[1], hi), *op[2:]] for op in ops
            if min(op[1], hi) > max(op[0], lo)]


def gaps(busy, lo, hi) -> list:
    """The stretches of ``[lo, hi]`` that ``busy`` (merged) leaves free."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append([t, min(s, hi)])
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append([t, hi])
    return [g for g in out if g[1] > g[0]]


def self_times(ops) -> list:
    """Each operation's duration less that of the operations nested in it
    (a ``while`` holds its body's operations on the same line), in the
    order given; ``ops`` are ``[start, end, ...]`` sorted by start, longest
    first on a tie."""
    own = [e - s for s, e, *_ in ops]
    stack = []  # (end, index) of the operations still open
    for i, (s, e, *_) in enumerate(ops):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            own[stack[-1][1]] -= min(e, stack[-1][0]) - s
        stack.append((e, i))
    return own


def op_group(name: str) -> str:
    """An operation's name without the numeric suffix XLA gives copies."""
    while _NUM_SUFFIX.search(name):
        name = _NUM_SUFFIX.sub("", name)
    return name


def op_label(op) -> str:
    """What the breakdown calls an operation: its kernel's source, else
    its ``tf_op`` (the JAX name stack), else its name less the suffix."""
    kernel = op[3] if len(op) > 3 else ""
    tf_op = op[4].rstrip(":") if len(op) > 4 else ""
    if kernel:
        return "kernel " + kernel
    return tf_op[-120:] if tf_op else op_group(op[2])


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------
class Trace:
    def __init__(self, raw: dict):
        self.spans = [tuple(s) for s in raw["spans"]]
        marks = [s for s in self.spans if s[2] == WINDOW]
        if not marks:
            raise ValueError(f"trace has no {WINDOW!r} span")
        self.lo, self.hi = marks[-1][0], marks[-1][1]
        self.devices = {
            name: sorted((op for op in ops
                          if op[1] > self.lo and op[0] < self.hi),
                         key=lambda op: (op[0], -op[1]))
            for name, ops in raw["devices"].items()
        }
        if not self.devices:
            raise ValueError("trace has no TPU device plane")
        self.busy = {name: clip(union(ops), self.lo, self.hi)
                     for name, ops in self.devices.items()}

    # -- whole window -------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        per = [sum(e - s for s, e in b) for b in self.busy.values()]
        return float(np.mean(per)) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    # -- selections ---------------------------------------------------------
    def span_intervals(self, name: str) -> list:
        return clip(union([s for s in self.spans if s[2] == name]),
                    self.lo, self.hi)

    def kernel_seconds(self, modules=(), exclude=()) -> float:
        """Summed device time, inside the window and averaged over the
        devices, of the Pallas kernels whose source lies in any of
        ``modules`` (any kernel where none are given) and in none of
        ``exclude``; a module is a path such as
        ``kernels/paged_decode.py``."""
        per = []
        for ops in self.devices.values():
            sel = clip([op for op in ops if len(op) > 3 and op[3]
                        and (not modules or any(m in op[3] for m in modules))
                        and not any(m in op[3] for m in exclude)],
                       self.lo, self.hi)
            per.append(sum(e - s for s, e in sel))
        return float(np.mean(per)) / 1e9

    def kernel_table(self) -> dict:
        """Device seconds in the window of each kernel source, on the first
        device."""
        out = defaultdict(float)
        name = sorted(self.devices)[0]
        for s, e, _, kernel, *_ in clip_ops(self.devices[name], self.lo,
                                           self.hi):
            if kernel:
                out[kernel] += (e - s) / 1e9
        return dict(out)

    def busy_within(self, intervals) -> float:
        """Device-busy seconds inside ``intervals`` (merged), averaged."""
        per = [overlap(b, intervals) for b in self.busy.values()]
        return float(np.mean(per)) / 1e9

    def idle_gaps(self) -> list:
        """``[start, end]`` of every idle stretch of the first device."""
        name = sorted(self.busy)[0]
        return gaps(self.busy[name], self.lo, self.hi)

    def labels(self, times) -> list:
        """For each of ``times`` (ascending), the innermost host span open
        then, not counting the window mark; ``idle`` where none is."""
        spans = sorted(sp for sp in self.spans if sp[2] != WINDOW)
        out, open_, k = [], [], 0
        for t in times:
            while k < len(spans) and spans[k][0] <= t:
                open_.append(spans[k])
                k += 1
            open_ = [sp for sp in open_ if sp[1] >= t]
            out.append(min(open_, key=lambda sp: sp[1] - sp[0])[2]
                       if open_ else "idle")
        return out

    def breakdown(self) -> dict:
        """The device operations that took most self time, and idle time
        by what the host was doing, each at most ``TOP`` entries, seconds,
        on the first device."""
        name = sorted(self.devices)[0]
        ops = clip_ops(self.devices[name], self.lo, self.hi)
        by_op = defaultdict(float)
        for op, own in zip(ops, self_times(ops)):
            by_op[op_label(op)] += own / 1e9
        by_host = defaultdict(float)
        idle = self.idle_gaps()
        for (g0, g1), what in zip(idle, self.labels(
                [(g0 + g1) / 2 for g0, g1 in idle])):
            by_host[what] += (g1 - g0) / 1e9
        top = lambda d: [[k, v] for k, v in  # noqa: E731
                         sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(by_op), "idle_gaps": top(by_host)}
