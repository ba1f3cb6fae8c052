"""XLA program accounting: recompile detection, cost models, numerics probes.

Three production failure modes that aggregate latency histograms cannot
see, each with its own detector here:

1. **Silent shape-bucket explosion.** Every distinct input shape a jitted
   function sees compiles a new XLA program; a bug in prefill bucketing or
   block-table padding turns a steady-state engine into a compile
   treadmill without changing any output. :class:`XLAAccounting.wrap`
   instruments a jitted callable: each call checks the jit cache size
   before/after and increments ``xla_compiles_total{program=}`` on a miss
   (plus ``xla_compile_seconds{program=}`` with the miss-call wall time).
   Steady-state decode must show this counter FLAT across ticks.

   A second, lower-level channel: :func:`install_compile_listener` hooks
   ``jax.monitoring``'s ``backend_compile`` duration event, attributing
   compiles to whichever :func:`tagged_program` region is active on the
   thread — this catches compiles inside code we don't wrap (autotune
   sweeps, library internals).

2. **Cost drift.** :func:`compiled_cost` pulls XLA's own
   ``cost_analysis()`` (flops / bytes accessed) for a lowered program, so
   bench_decode can cross-check its analytic bytes/token model against
   what the compiler actually scheduled (``xla_cost_bytes``).

3. **Numerical poisoning.** A single Inf in the landmark (m, l)
   online-softmax stats silently corrupts every later tick on that lane.
   :class:`NumericsProbe` counts non-finite values per probe site
   (``numerics_nonfinite_total{site=}``); the engine calls it every
   ``ServeConfig.numerics_probe_every`` ticks on logits and the (m, l)
   stream stats. Off (0) by default — the probe forces a device sync.

Like kernels/dispatch.py, this module routes through a module-level
registry holder so instrumentation is a no-op until telemetry is enabled.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Optional

import numpy as np

from repro.telemetry.metrics import RegistrySlot

_METRICS = RegistrySlot()
_LISTENER_INSTALLED = False
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_tls = threading.local()


def set_metrics(registry) -> None:
    """Point module-level accounting (the jax.monitoring listener) at a
    live registry, held weakly (``RegistrySlot``). Pass ``None`` to restore
    the null registry."""
    _METRICS.set(registry)


def current_program() -> str:
    """Name of the innermost active :func:`tagged_program` region."""
    stack = getattr(_tls, "programs", None)
    return stack[-1] if stack else "untagged"


@contextlib.contextmanager
def tagged_program(name: str):
    """Attribute any backend compile that fires inside this region to
    ``name`` (thread-local; regions nest, innermost wins)."""
    stack = getattr(_tls, "programs", None)
    if stack is None:
        stack = _tls.programs = []
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def install_compile_listener() -> None:
    """Register the jax.monitoring backend-compile listener (idempotent —
    jax offers no unregister, so one process-wide hook routes through the
    module registry holder)."""
    global _LISTENER_INSTALLED
    if _LISTENER_INSTALLED:
        return
    try:
        from jax import monitoring
    except ImportError:  # pragma: no cover - jax always present here
        return

    def _on_duration(event: str, duration: float, **kw) -> None:
        if _COMPILE_EVENT not in event:
            return
        program = current_program()
        registry = _METRICS.get()
        registry.counter(
            "xla_backend_compiles_total",
            help="backend compiles observed via jax.monitoring",
            labels=("program",)).labels(program=program).inc()
        registry.histogram(
            "xla_backend_compile_seconds",
            help="backend compile durations via jax.monitoring",
            buckets=(0.01, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0),
        ).observe(duration)

    monitoring.register_event_duration_secs_listener(_on_duration)
    _LISTENER_INSTALLED = True


def _cache_size_fn(fn):
    """Resolve a jit-cache-size probe for ``fn``: jitted functions expose
    ``_cache_size`` directly; factory closures (serve/paged.py) expose the
    inner jitted function as ``fn._jitted``."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        inner = getattr(fn, "_jitted", None)
        probe = getattr(inner, "_cache_size", None)
    return probe


class XLAAccounting:
    """Per-program compile counters over wrapped jitted callables."""

    def __init__(self, registry):
        self._registry = registry
        self._compiles = registry.counter(
            "xla_compiles_total",
            help="jit cache misses per instrumented program",
            labels=("program",))
        self._calls = registry.counter(
            "xla_program_calls_total",
            help="calls per instrumented program",
            labels=("program",))
        self._compile_s = registry.histogram(
            "xla_compile_seconds",
            help="wall time of calls that triggered a compile",
            labels=("program",),
            buckets=(0.01, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0))

    def wrap(self, fn, program: str):
        """Instrument a jitted callable (or a closure exposing
        ``_jitted``): count calls, detect cache-size growth as a compile,
        and tag the region so the backend-compile listener attributes
        correctly. Returns ``fn`` untouched when no cache probe exists."""
        probe = _cache_size_fn(fn)
        if probe is None:
            return fn
        calls = self._calls.labels(program=program)
        compiles = self._compiles.labels(program=program)
        compile_s = self._compile_s.labels(program=program)

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            calls.inc()
            before = probe()
            t0 = time.perf_counter()
            with tagged_program(program):
                out = fn(*args, **kwargs)
            if probe() > before:
                compiles.inc()
                compile_s.observe(time.perf_counter() - t0)
            return out

        wrapped._jitted = getattr(fn, "_jitted", fn)
        return wrapped

    def compiles(self, program: str) -> int:
        return int(self._compiles.labels(program=program).value)


def compiled_cost(fn, *args, **kwargs) -> dict:
    """XLA's own cost model for ``fn(*args, **kwargs)``:
    ``{"flops": float, "bytes": float}`` from ``cost_analysis()`` after
    lowering+compiling (AOT — does not execute). Returns zeros when the
    backend offers no analysis."""
    cost = fn.lower(*args, **kwargs).compile().cost_analysis()
    if not isinstance(cost, dict):
        return {"flops": 0.0, "bytes": 0.0}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
    }


class NumericsProbe:
    """NaN/Inf counters per probe site. ``check`` pulls the array to host
    (device sync!) — gate call frequency at the call site."""

    def __init__(self, registry):
        self._nonfinite = registry.counter(
            "numerics_nonfinite_total",
            help="non-finite elements observed per probe site",
            labels=("site",))
        self._checks = registry.counter(
            "numerics_checks_total", help="numerics probe invocations")
        self.last_bad: Optional[str] = None

    def check(self, site: str, arr) -> int:
        """Count non-finite elements of ``arr`` under ``site``; returns
        the count and remembers the most recent offending site."""
        self._checks.inc()
        host = np.asarray(arr)
        if host.dtype.kind not in "fc":
            return 0
        bad = int(host.size - np.count_nonzero(np.isfinite(host)))
        if bad:
            self._nonfinite.labels(site=site).inc(bad)
            self.last_bad = site
        return bad


class NullNumericsProbe:
    """Disabled twin — never syncs, never counts."""

    last_bad = None

    def check(self, site: str, arr) -> int:
        return 0
