"""Garbage-collector pauses as telemetry.

A collection stops every Python thread, so a long one holds up the engine
tick or the training step that triggered it, and with it the chip. One
process-wide ``gc.callbacks`` hook, installed once like the compile
listener in ``accounting.py``, routes each collection to the newest
enabled ``Telemetry`` through a weak slot (a dropped bundle is never kept
alive by it, and a collection is never counted twice):

* every collection adds its seconds to
  ``gc_pause_seconds_total{generation}``;
* a generation-2 (full) collection also records a ``gc`` span, through
  ``jax.profiler.TraceAnnotation`` when the tracer annotates, so device
  idle time during one is labelled ``gc`` in a profile.

Per collection the hook does two ``perf_counter`` calls and one add. It
never calls into the registry: the collection may have interrupted a
thread holding the registry's lock, so the counters are resolved when the
sink is made.
"""
from __future__ import annotations

import gc
import time
import weakref

FULL = 2  # the generation of a full collection


class GCSink:
    """What the hook writes into: one counter per generation and the
    tracer of one ``Telemetry``. The bundle holds it; the slot only
    refers to it."""

    __slots__ = ("counters", "tracer", "annotate", "__weakref__")

    def __init__(self, registry, tracer):
        family = registry.counter(
            "gc_pause_seconds_total",
            help="seconds the garbage collector held the process",
            labels=("generation",))
        self.counters = tuple(family.labels(generation=g)
                              for g in range(FULL + 1))
        self.tracer = tracer
        self.annotate = None  # makes the profiler annotation, if tracing
        if tracer.annotate:
            import jax

            self.annotate = jax.profiler.TraceAnnotation


class _Hook:
    """The one ``gc.callbacks`` entry of the process: the newest sink,
    held weakly, and the collection under way."""

    def __init__(self):
        self.sink = lambda: None
        self.t0 = 0.0
        self.annotation = None  # open over the full collection under way

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if info["generation"] == FULL:
                sink = self.sink()
                if sink is not None and sink.annotate is not None:
                    self.annotation = sink.annotate("gc")
                    self.annotation.__enter__()
            self.t0 = time.perf_counter()
            return
        seconds = time.perf_counter() - self.t0
        sink = self.sink()
        if sink is not None:
            sink.counters[info["generation"]].inc(seconds)
            if info["generation"] == FULL:
                sink.tracer.interval("gc", self.t0, seconds)
        if self.annotation is not None:
            self.annotation.__exit__(None, None, None)
            self.annotation = None


HOOK = _Hook()


def watch(registry, tracer) -> GCSink:
    """Route every later collection to a new sink over ``registry`` and
    ``tracer`` (installing the hook on first use); the caller keeps the
    returned sink alive for as long as it wants the pauses."""
    sink = GCSink(registry, tracer)
    HOOK.sink = weakref.ref(sink)
    if HOOK not in gc.callbacks:
        gc.callbacks.append(HOOK)
    return sink
