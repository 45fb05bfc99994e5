"""Observability plane: metrics registry + flight recorder (Stage 8).

The serving stack (and the trainer, and the benchmarks) report through
one substrate instead of three ad-hoc idioms:

* ``metrics``  — dependency-free Counter / Gauge / Histogram registry
  with JSON-snapshot and Prometheus-text serialization;
* ``flight``   — a JSONL flight recorder of typed per-request
  lifecycle events + per-tick engine snapshots, replayable offline;
* ``Observability`` — the bundle a component takes as one argument:
  registry + recorder + clock, and ``span``, the one way a component
  times its own phases.

Everything here is stdlib-only and import-safe before jax.
"""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass, field

from .flight import (EVENT_FIELDS, NULL, FlightRecorder,
                     NullFlightRecorder, parse_events, read_events,
                     replay_summary)
from .metrics import (LATENCY_MS_BUCKETS, TIME_S_BUCKETS, Counter, Gauge,
                      Histogram, MetricsRegistry, exp_buckets)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "exp_buckets", "LATENCY_MS_BUCKETS", "TIME_S_BUCKETS",
           "FlightRecorder", "NullFlightRecorder", "NULL",
           "EVENT_FIELDS", "parse_events", "read_events",
           "replay_summary", "Observability", "Span", "PHASE_SECONDS",
           "PHASE_SPANS"]

PHASE_SECONDS = "serving_phase_seconds_total"
PHASE_SPANS = "serving_phase_spans_total"


@dataclass
class Observability:
    """What a component needs to report: one registry, one recorder,
    one clock.  The default is the *cheap always-on* configuration —
    counters, phase spans and latency histograms record (they are a
    handful of float ops per tick) and the flight recorder is the
    no-op ``NULL``, so a bare ``ServingEngine`` pays nothing
    measurable for its metrics plane.

    ``flight_path`` is the convenience constructor for the common
    case: ``Observability(flight_path="flight.jsonl")`` builds a real
    recorder on the bundle's clock."""
    registry: MetricsRegistry = field(default_factory=MetricsRegistry)
    flight: object = NULL
    clock: object = time.perf_counter
    flight_path: object = None
    _phases: dict = field(default_factory=dict, init=False, repr=False)

    def __post_init__(self):
        if self.flight_path is not None and self.flight is NULL:
            self.flight = FlightRecorder(self.flight_path,
                                         clock=self.clock)

    def span(self, name: str, **args) -> "Span":
        """Time one phase: ``with obs.span("serving.fetch"): ...``.

        Adds the host-clock seconds to
        ``serving_phase_seconds_total{phase=name}`` and one to
        ``serving_phase_spans_total{phase=name}``; while a profiler
        trace is being taken it is also a
        ``jax.profiler.TraceAnnotation`` named ``name`` with ``args`` as
        metadata, on the clock of the device trace.  Spans nest; a
        span's ``seconds`` holds its duration once it has exited."""
        phase = self._phases.get(name)
        if phase is None:
            reg = self.registry
            phase = self._phases[name] = (
                reg.counter(PHASE_SECONDS, phase=name),
                reg.counter(PHASE_SPANS, phase=name))
        return Span(name, args, phase, self.clock)

    def phase_seconds(self) -> dict:
        """{phase: seconds} summed over every span taken so far."""
        return {name: c.value for name, (c, _) in self._phases.items()}

    @property
    def flight_enabled(self) -> bool:
        return self.flight.enabled

    def close(self) -> None:
        self.flight.close()


class Span:
    """One timed phase (``Observability.span``)."""
    __slots__ = ("name", "args", "_phase", "_clock", "_t0", "_ann",
                 "seconds")

    def __init__(self, name, args, phase, clock):
        self.name, self.args = name, args
        self._phase, self._clock = phase, clock
        self._ann = None
        self.seconds = 0.0

    def __enter__(self) -> "Span":
        # Without jax imported no profiler can be running, and importing
        # it here would break this package's promise to load before jax.
        prof = sys.modules.get("jax.profiler")
        if prof is not None and prof.TraceAnnotation.is_enabled():
            self._ann = prof.TraceAnnotation(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = self._clock() - self._t0
        seconds, spans = self._phase
        seconds.inc(self.seconds)
        spans.inc()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
