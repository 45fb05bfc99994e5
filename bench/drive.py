"""Drive ``ServingEngine`` with one traffic mix for a measured window.

The loop of the mix's arrival process (``bench/loops/<kind>.py``) calls
``Client.submit`` and ``Client.step``.  Each step records its host-clock
span and the work it completed (the kind's ``on_output`` counts it),
and each request its due time and the times its outputs came back.
Outputs come back when ``step`` returns, so every output of a step
carries that step's end time.  Around each step, outside its host span,
the client also reads what the engine reports about itself: the seconds
of each of its ``serving.*`` phases (``obs.phase_seconds()``) and its
counters, and puts the step's differences on the ``Tick``.  Host spans
around submit and step go into the profiler's trace when one is running.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

clock = time.perf_counter


@dataclass
class Tick:
    t0: float
    t1: float
    prefills: list = field(default_factory=list)   # prompt rows per prefill
    decodes: list = field(default_factory=list)    # context per decode token
    images: int = 0
    served: list = field(default_factory=list)     # CNN: (image, class)
    live: int = 0                                  # slots that advanced
    finished: int = 0                              # requests completed
    traced: bool = False
    phases: dict = field(default_factory=dict)     # serving.* phase -> s
    counters: dict = field(default_factory=dict)   # flat name -> delta


@dataclass
class Rec:
    """One request as the client sees it."""
    uid: int
    item: int                       # index into the traffic's inputs
    prompt_len: int
    due: float = 0.0                # host clock time it was due
    times: list = field(default_factory=list)   # host time of each output
    out: list = field(default_factory=list)     # served tokens / class


@dataclass
class Window:
    start: float = 0.0
    end_mark: float = float("inf")  # the steps begun before this ...
    end: float = 0.0                # ... end here: the window's close
    ticks: list = field(default_factory=list)
    recs: dict = field(default_factory=dict)    # uid -> Rec
    trace_span: tuple | None = None             # host (start, stop)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def counter_values(registry) -> dict:
    """{(name, labels): value} of every counter of the engine's
    ``MetricsRegistry``, without the histogram percentiles that its
    ``snapshot()`` computes (about 70 us a call on a CPU core)."""
    return {(fam.name, labels): c.value
            for fam in registry._families.values() if fam.kind == "counter"
            for labels, c in fam.children.items()}


@functools.lru_cache(maxsize=None)
def flat_name(key: tuple) -> str:
    """A counter's name as ``snapshot()`` gives it:
    ``serving_phase_spans_total{phase="serving.step"}``."""
    name, labels = key
    if not labels:
        return name
    return name + "{" + ",".join(f'{k}="{v}"' for k, v in labels) + "}"


def _moved(after: dict, before: dict) -> dict:
    """The entries of ``after`` that differ from ``before``, as
    differences."""
    return {k: v - before.get(k, 0.0) for k, v in after.items()
            if v != before.get(k, 0.0)}


class Client:
    """Feeds ``engine`` from ``traffic`` and records what comes back.

    ``kind`` is the model's module (``bench/kinds``); ``tracer``
    (optional) has ``start()``/``stop()``; it is switched on ``lead_s``
    into the window for ``trace_s`` seconds, between steps."""

    def __init__(self, engine, traffic, kind, loop, *, tracer=None,
                 lead_s: float = 0.0, trace_s: float = 0.0):
        self.eng = engine
        self.traffic = traffic
        self.kind = kind
        self.loop = loop
        self.tracer = tracer
        self.lead_s, self.trace_s = lead_s, trace_s
        self.w = Window()
        self._next_uid = 0
        self._tracing = False
        self._in_window = False

    def queued(self) -> int:
        return len(self.eng.admission) + len(self.eng.queue)

    def submit(self) -> None:
        uid = self._next_uid
        self._next_uid += 1
        req, rec = self.kind.request(self.traffic, uid)
        rec.due = clock()
        self.w.recs[uid] = rec
        req.bench_rec = rec
        self.eng.submit(req)

    def trace_switch(self, now: float) -> None:
        if self.tracer is None:
            return
        t = now - self.w.start
        if not self._tracing and self.w.trace_span is None \
                and t >= self.lead_s:
            self.tracer.start()
            self._tracing = True
            self.w.trace_span = (clock(), None)
        elif self._tracing and t >= self.lead_s + self.trace_s:
            self.stop_trace()

    def stop_trace(self) -> None:
        if self._tracing:
            self.w.trace_span = (self.w.trace_span[0], clock())
            self.tracer.stop()
            self._tracing = False

    def step(self) -> Tick:
        obs = self.eng.obs
        phases, counters = obs.phase_seconds(), counter_values(obs.registry)
        with TraceAnnotation("bench.step"):
            t0 = clock()
            finished = self.eng.step()
            t1 = clock()
        moved = _moved(counter_values(obs.registry), counters)
        tick = Tick(t0, t1, traced=self._tracing,
                    phases=_moved(obs.phase_seconds(), phases),
                    counters={flat_name(k): v for k, v in moved.items()})
        for req in list(self.eng.live.values()) + list(finished):
            rec = req.bench_rec
            before, after = len(rec.out), len(req.out_tokens)
            if after == before:
                continue
            rec.out.extend(int(x) for x in req.out_tokens[before:after])
            rec.times.extend([t1] * (after - before))
            self.kind.on_output(tick, rec, before, after)
        tick.finished = len(finished)
        tick.live = len(self.eng.live) + len(finished)
        if self._in_window:
            self.w.ticks.append(tick)
        return tick

    def prime(self, n: int) -> None:
        """Set-up: submit the next ``n`` requests and take one step,
        outside the window."""
        for _ in range(n):
            self.submit()
        self.step()

    def run(self, seconds: float) -> Window:
        self.w.start = clock()
        self.w.end_mark = self.w.start + seconds
        self._in_window = True
        self.loop(self)
        self._in_window = False
        self.w.end = max((t.t1 for t in self.w.ticks), default=self.w.start)
        self.stop_trace()
        return self.w
