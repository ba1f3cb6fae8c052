"""Unified telemetry: metrics registry + tick tracing + drift monitors.

``Telemetry`` is the one object the engine/trainer/benchmarks hold. It
bundles a :class:`~repro.telemetry.metrics.MetricsRegistry` and a
:class:`~repro.telemetry.tracing.Tracer` and exposes the two export paths
the rest of the stack (and CI) consume:

* ``snapshot()`` — nested dict of every metric sample plus span-buffer
  counters; cheap, safe to call mid-run.
* ``dump_jsonl(path)`` — one self-describing JSONL file: a ``meta`` line,
  one ``metric`` line per (name, label-set), one ``span`` line per traced
  event. This is the artifact CI uploads and the offline-analysis input.

``Telemetry(enabled=False)`` (or :func:`null_telemetry`) swaps in the
no-op registry/tracer pair: every instrumentation site still *calls*
telemetry, but each call is a shared-object no-op, nothing is retained,
and dumps write nothing — the zero-overhead contract behind the
``ServeConfig.telemetry`` knob.

The PR 7 observability layer adds three more members to the bundle:

* ``flight`` — a :class:`~repro.telemetry.flight.FlightRecorder` holding
  one bounded lifeline per request; its lifelines are appended to the
  JSONL dump as ``{"kind": "flight", ...}`` lines and drive the request
  tracks in the Perfetto export (:mod:`repro.telemetry.export`);
* ``meta_defaults`` — the provenance stamp (git SHA, jax version,
  config hash) merged into every ``dump_jsonl`` meta line and exported
  trace; populate it via :func:`stamp_provenance`;
* the flight recorder shares the tracer's ``perf_counter`` origin so
  lifelines and host spans share one timeline.

An enabled bundle also becomes the one that garbage-collector pauses are
written to (:mod:`repro.telemetry.gc_pauses`): the
``gc_pause_seconds_total{generation}`` counter and a ``gc`` span per full
collection. A disabled one installs nothing.
"""
from __future__ import annotations

import json
from typing import Optional

from repro.telemetry.accounting import (  # noqa: F401
    NullNumericsProbe,
    NumericsProbe,
    XLAAccounting,
    compiled_cost,
    install_compile_listener,
    tagged_program,
)
from repro.telemetry import gc_pauses
from repro.telemetry.export import (  # noqa: F401
    chrome_trace,
    validate_trace,
    write_chrome_trace,
)
from repro.telemetry.flight import FlightRecorder, NullFlightRecorder  # noqa: F401
from repro.telemetry.metrics import (  # noqa: F401  (re-exports)
    LATENCY_BUCKETS,
    RATIO_BUCKETS,
    TICK_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    exp_buckets,
)
from repro.telemetry.monitors import (  # noqa: F401
    DriftMonitor,
    SpectrumMonitor,
    bv_from_stats,
    bv_row_residual,
    spectrum_mass,
)
from repro.telemetry.provenance import config_hash, git_sha, provenance  # noqa: F401
from repro.telemetry.tracing import NullTracer, Tracer  # noqa: F401


class Telemetry:
    """Bundle of one metrics registry + one tracer with JSONL export."""

    def __init__(
        self,
        enabled: bool = True,
        *,
        registry: Optional[MetricsRegistry] = None,
        annotate: bool = False,
        max_events: int = 200_000,
    ):
        self.enabled = enabled
        self.meta_defaults: dict = {}
        if enabled:
            self.metrics = registry if registry is not None else MetricsRegistry()
            self.tracer = Tracer(
                self.metrics, annotate=annotate, max_events=max_events
            )
            self.flight = FlightRecorder(
                registry=self.metrics, origin=self.tracer._origin
            )
            self._gc = gc_pauses.watch(self.metrics, self.tracer)
        else:
            self.metrics = NullRegistry()
            self.tracer = NullTracer()
            self.flight = NullFlightRecorder()

    def stamp_provenance(self, *cfgs) -> None:
        """Record the provenance stamp (git SHA, jax version, and the
        joint hash of ``cfgs``) into ``meta_defaults`` so every later
        ``dump_jsonl``/trace export carries it."""
        if self.enabled:
            self.meta_defaults.update(provenance(*cfgs))

    def span(self, name: str, **labels):
        return self.tracer.span(name, **labels)

    def step_span(self, name: str, step: int):
        return self.tracer.step_span(name, step)

    def snapshot(self) -> dict:
        return {"metrics": self.metrics.snapshot(), "spans": self.tracer.summary()}

    def dump_jsonl(self, path, meta: Optional[dict] = None) -> int:
        """Write the full telemetry state as JSONL; returns lines written.
        Disabled telemetry writes nothing (and creates no file)."""
        if not self.enabled:
            return 0
        n = 0
        with open(path, "w") as fh:
            head = {"kind": "meta", "schema": "repro-telemetry-v1"}
            head.update(self.meta_defaults)
            if meta:
                head.update(meta)
            fh.write(json.dumps(head) + "\n")
            n += 1
            for name, kind, labels, sample in self.metrics.iter_samples():
                row = {"kind": "metric", "name": name, "type": kind}
                if labels:
                    row["labels"] = labels
                row.update(sample)
                fh.write(json.dumps(row) + "\n")
                n += 1
            n += self.tracer.dump_jsonl(fh)
            n += self.flight.dump_jsonl(fh)
        return n


def null_telemetry() -> Telemetry:
    return Telemetry(enabled=False)
