#!/usr/bin/env python3
"""Put a cell's time down to the program's own phases and ops.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--out DIR]

Runs the cell as ``bench/run.py`` does (same traffic, weights, engine,
warm-up, priming and window, with the cell's traced part) and reads
what the program reports about itself:

* per step, the seconds of each ``serving.*`` phase: the engine's
  ``obs.span`` counters (``serving_phase_seconds_total{phase}``), read
  before and after each step, outside the step's host span;
* from the profiler trace, the host spans ``bench.*`` and ``serving.*``
  and each device op's ProgramOp scope, ``<kernel>.<name>``, which the
  executor's ``jax.named_scope`` writes into the op's HLO ``op_name``.

It prints one JSON object: each phase's mean milliseconds per step
before, during and after the trace, the client's time outside
``serving.step``, the shares ``stage.cnn``, ``sample.decode`` and
``kv_copy.decode``, the share of the traced device-busy time in scoped
ops, device time by op scope, the longest idle gaps labelled with the
innermost span, and the host cost of one span with the profiler off.  With ``--out DIR`` it
also writes every step's phases (``<cell>.ticks.json``) and an extract
of the first three traced steps (``<cell>.json.gz``, ``extract``), the
form ``bench/tests/data/traces`` holds.

Nothing in ``bench/run.py`` reads this module: the benchmark's
``Tick`` and ``trace_reduce.load`` do not carry phases or scopes.
"""
from __future__ import annotations

import argparse
import bisect
import gzip
import json
import os
import re
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import drive, spec, trace_reduce  # noqa: E402

HOST_PREFIXES = ("bench.", "serving.")
STEP_PHASE = "serving.step"
# The stat of a device op's event metadata that carries the HLO
# instruction's op_name (``jit(program_decode)/decode_attention.l3.attn/
# transpose:``); its ``program_id`` stat names the HLO module.
SCOPE_STAT = "tf_op"
# Ops that only move or re-lay data.
COPY_KINDS = ("copy", "copy-start", "copy-done", "transpose")


# --- per-step phases (program counters) --------------------------------------------

class PhaseClient(drive.Client):
    """``drive.Client`` whose ticks also carry ``phases``: the seconds
    of each ``serving.*`` phase the engine spent in that step."""

    def step(self) -> drive.Tick:
        before = self.eng.obs.phase_seconds()
        tick = super().step()
        after = self.eng.obs.phase_seconds()
        tick.phases = {k: v - before.get(k, 0.0) for k, v in after.items()
                       if v != before.get(k, 0.0)}
        return tick


def before_trace(ticks) -> list:
    """The ticks before the first traced one.  Once the profiler has
    run, the process stays changed: alexnet-owt's ``serving.stage``
    takes about 40 ms a batch before a trace and about 4 ms after it
    (TPU v5e), so untraced ticks after the trace are not the ones an
    untraced run has."""
    for i, t in enumerate(ticks):
        if t.traced:
            return ticks[:i]
    return list(ticks)


def phase_share(ticks, names) -> float | None:
    """% of the ``serving.step`` seconds of the ticks before the trace
    spent in the phases ``names``."""
    ticks = before_trace(ticks)
    step = sum(t.phases.get(STEP_PHASE, 0.0) for t in ticks)
    if step <= 0:
        return None
    return 100.0 * sum(t.phases.get(n, 0.0) for t in ticks
                       for n in names) / step


def stage_cnn(ticks) -> float | None:
    """stage.cnn: batching, stacking, padding and the transfer to the
    device (``serving.stage``) over the steps' time before the trace,
    in %."""
    return phase_share([t for t in ticks if t.images], ["serving.stage"])


def sample_decode(ticks) -> float | None:
    """sample.decode: the logits fetch and the host's sampling
    (``serving.fetch`` + ``serving.sample``) over the decode steps'
    time before the trace, in %."""
    return phase_share([t for t in ticks if t.decodes],
                       ["serving.fetch", "serving.sample"])


def phase_ms(ticks) -> dict:
    """{phase: mean ms per step} over ``ticks``."""
    if not ticks:
        return {}
    names = sorted({n for t in ticks for n in t.phases})
    return {n: 1e3 * sum(t.phases.get(n, 0.0) for t in ticks) / len(ticks)
            for n in names}


# --- scoped device ops (device trace) ----------------------------------------------

def scope_of(op_name: str | None) -> str | None:
    """The ProgramOp scope in an HLO op_name
    (``jit(program_decode)/decode_attention.l3.attn/transpose:`` ->
    ``decode_attention.l3.attn``): the first component inside the
    runner's ``jit(...)``, where it is a ``<kernel>.<name>``.  Ops that
    no ProgramOp emitted have none: a layout copy of an argument
    (``state[27]:``), or the runner's own ops (``jit(program_decode)/
    jit(_where)/select_n:``)."""
    if not op_name:
        return None
    parts = op_name.rstrip(":").split("/")
    if parts[0].startswith("jit("):
        parts = parts[1:]
    if len(parts) < 2 or "." not in parts[0] or "(" in parts[0]:
        return None
    return parts[0]


def _scope_label(op_name: str | None) -> str:
    """What ``by_scope`` files an op under: its scope's kernel, else
    ``(argument)`` for a copy of a runner argument, else ``(none)``."""
    scope = scope_of(op_name)
    if scope:
        return scope.split(".", 1)[0]
    if op_name and "/" not in op_name:
        return "(argument)"
    return "(none)"


@dataclass
class ScopedTrace(trace_reduce.Trace):
    """``trace_reduce.Trace`` over the ``bench.*`` and ``serving.*``
    host spans, whose ops also carry their HLO op_name and module
    (``meta``: op tuple -> (op_name, module)).  Steps, busy time, kernel
    time and top ops are as the benchmark's; an idle gap is labelled
    with the innermost span covering it, a program phase where one
    does."""
    meta: dict = field(default_factory=dict)

    def step_ops(self) -> list:
        """The ops that start inside a traced step."""
        starts = [o[0] for o in self.ops]
        out = []
        for a, b in self.steps:
            out += self.ops[bisect.bisect_left(starts, a):
                            bisect.bisect_left(starts, b)]
        return out

    def step_busy_s(self) -> float:
        return sum(self.busy_within(a, b) for a, b in self.steps)

    def scope(self, op) -> str | None:
        return scope_of(self.meta.get(op, (None, None))[0])

    def scoped_seconds(self, prefix: str, kinds) -> float:
        """Device seconds of the traced steps' ops of ``kinds`` whose
        scope starts with ``prefix``."""
        return sum(o[1] - o[0] for o in self.step_ops()
                   if self.kind_of[o[2]] in kinds
                   and (self.scope(o) or "").startswith(prefix))

    def scoped_share(self) -> float | None:
        """% of the traced steps' device-busy time in ops that carry a
        ProgramOp scope."""
        busy = self.step_busy_s()
        if busy <= 0:
            return None
        scoped = trace_reduce._union(
            [(o[0], o[1]) for o in self.step_ops() if self.scope(o)])
        return 100.0 * sum(e - s for s, e in scoped) / busy

    def by_scope(self, top: int = 12) -> list:
        """Device seconds of the traced steps by op kind within scope
        kind (``decode_attention/copy``, ``(argument)/copy``), the layer
        index dropped."""
        by: dict = {}
        for o in self.step_ops():
            head = _scope_label(self.meta.get(o, (None, None))[0])
            key = f"{head}/{self.kind_of[o[2]]}"
            by[key] = by.get(key, 0.0) + o[1] - o[0]
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:top]]


def kv_copy_decode(tr: ScopedTrace | None) -> float | None:
    """kv_copy.decode: device time of the copies and transposes inside
    the decode attention ops' scopes over the traced steps' device-busy
    time, in %."""
    if tr is None or not tr.steps:
        return None
    busy = tr.step_busy_s()
    if busy <= 0:
        return None
    return 100.0 * tr.scoped_seconds("decode_attention.", COPY_KINDS) / busy


def _xspace_class():
    """A protobuf class for the part of the profiler's ``XSpace`` that
    holds each device op's metadata: an op's HLO ``op_name`` and module
    are stats of its event metadata, which ``ProfileData`` does not
    show.  Field numbers as in tsl's ``xplane.proto``; the fields left
    out are skipped when parsing."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, typ, rep, sub in fields:
            m.field.add(name=fname, number=num, type=typ,
                        label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL,
                        type_name=f".bench_xplane.{sub}" if sub else None)
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, 0, None),
        ("uint64_value", 3, F.TYPE_UINT64, 0, None),
        ("int64_value", 4, F.TYPE_INT64, 0, None),
        ("str_value", 5, F.TYPE_STRING, 0, None),
        ("ref_value", 7, F.TYPE_UINT64, 0, None))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None),
        ("stats", 5, F.TYPE_MESSAGE, 1, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, 0, None),
        ("name", 2, F.TYPE_STRING, 0, None))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, 0, None),
        ("value", 2, F.TYPE_MESSAGE, 0, "XStatMetadata"))
    msg("XEvent", ("metadata_id", 1, F.TYPE_INT64, 0, None))
    msg("XLine", ("name", 2, F.TYPE_STRING, 0, None),
        ("events", 4, F.TYPE_MESSAGE, 1, "XEvent"))
    msg("XPlane", ("name", 2, F.TYPE_STRING, 0, None),
        ("lines", 3, F.TYPE_MESSAGE, 1, "XLine"),
        ("event_metadata", 4, F.TYPE_MESSAGE, 1, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, 1, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, 1, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


_MODULE = re.compile(r"^(\S+)\((\d+)\)$")     # jit_program_decode(1323...)


def op_metadata(data: bytes) -> dict:
    """{device plane name: [(op_name, module) of each event of its
    "XLA Ops" line, in order]} from a serialized ``XSpace``: the
    op_name from the ``SCOPE_STAT`` stat of the event's metadata, the
    module from its ``program_id`` stat and the plane's module names."""
    xs = _xspace_class()()
    xs.ParseFromString(data)
    out = {}
    for plane in xs.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        stat_name = {e.key: e.value.name for e in plane.stat_metadata}
        modules = {}
        for e in plane.event_metadata:
            m = _MODULE.match(e.value.name)
            if m:
                modules[int(m.group(2))] = m.group(1)
        meta = {}
        for e in plane.event_metadata:
            stats = {}
            for st in e.value.stats:
                name = stat_name.get(st.metadata_id)
                if name == SCOPE_STAT:
                    stats[name] = (stat_name.get(st.ref_value)
                                   if st.ref_value else st.str_value)
                elif name == "program_id":
                    stats[name] = st.uint64_value or st.int64_value
            meta[e.key] = (stats.get(SCOPE_STAT),
                           modules.get(stats.get("program_id")))
        for line in plane.lines:
            if line.name == trace_reduce.OPS_LINE:
                out[plane.name] = [meta.get(ev.metadata_id, (None, None))
                                   for ev in line.events]
    return out


def load(path: str) -> ScopedTrace:
    """``trace_reduce.load``, keeping ``serving.*`` spans and each op's
    op_name and module."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    scopes = op_metadata(data)
    ops, spans, meta, n_dev = [], [], {}, 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            n_dev += 1
            for line in plane.lines:
                if line.name != trace_reduce.OPS_LINE:
                    continue
                for ev, m in zip(line.events, scopes[plane.name],
                                 strict=True):
                    s = ev.start_ns * 1e-9
                    op = (s, s + ev.duration_ns * 1e-9, ev.name)
                    ops.append(op)
                    meta[op] = m
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return ScopedTrace(ops, spans, max(n_dev, 1), meta)


_OPCODE_END = re.compile(r"(\(.*?\)|\S+) [\w\-]+\(")


def _short(name: str) -> str:
    """An op's HLO text cut after its opcode, unless it is a Pallas
    call, whose operands tell the kernel (``trace_reduce.KERNELS``)."""
    if trace_reduce.TPU_CALL in name or " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE_END.match(rhs)
    return f"{lhs} = {rhs[:m.end()]})" if m else name


def extract(tr: ScopedTrace, steps: int) -> dict:
    """The first ``steps`` traced steps of ``tr``: the device ops that
    start in them (HLO text cut after the opcode, op_name, module) and
    the host spans inside them, in ns from the first step's start.
    Strings are stored once and referred to by index; op starts are
    stored as the difference from the op before."""
    a, b = tr.steps[0][0], tr.steps[steps - 1][1]
    names: dict = {}

    def idx(x):
        return names.setdefault(x, len(names))

    def ns(t):
        return round((t - a) * 1e9)
    ops = [o for o in tr.ops if a <= o[0] < b]
    starts = [ns(o[0]) for o in ops]
    meta = [tr.meta.get(o, (None, None)) for o in ops]
    return {
        "steps": steps,
        "op_start_delta": [x - y for x, y in zip(starts, [0] + starts)],
        "op_ns": [ns(o[1]) - x for o, x in zip(ops, starts)],
        "op_text": [idx(_short(o[2])) for o in ops],
        "op_name": [idx(m[0]) for m in meta],
        "op_module": [idx(m[1]) for m in meta],
        "spans": [[ns(s), ns(e), idx(n)] for s, e, n in tr.spans
                  if a <= s and e <= b],
        "names": list(names)}


def from_extract(d: dict) -> ScopedTrace:
    names = d["names"]
    ops, meta, t = [], {}, 0
    for dt, dur, text, op_name, module in zip(
            d["op_start_delta"], d["op_ns"], d["op_text"], d["op_name"],
            d["op_module"], strict=True):
        t += dt
        op = (t * 1e-9, (t + dur) * 1e-9, names[text])
        ops.append(op)
        meta[op] = (names[op_name], names[module])
    spans = [(s * 1e-9, e * 1e-9, names[n]) for s, e, n in d["spans"]]
    return ScopedTrace(ops, spans, 1, meta)


# --- the cost of a span ------------------------------------------------------------

def span_cost_us(n: int = 200_000) -> float:
    """Host microseconds of one ``obs.span`` enter and exit, with no
    profiler running."""
    from repro.obs import Observability
    obs = Observability()
    span = obs.span
    t0 = time.perf_counter()
    for _ in range(n):
        with span("serving.cost"):
            pass
    return 1e6 * (time.perf_counter() - t0) / n


# --- the run -----------------------------------------------------------------------

def run_phases(cell, seed: int, seconds: float, devs, out: Path | None,
               extract_steps: int = 3) -> dict:
    import statistics

    from bench import run as bench_run
    from bench import traffic, weights
    from repro.obs import PHASE_SPANS
    config, tspec = cell.config, cell.traffic
    kind = spec.kind(config)
    t = traffic.make(tspec, config, seed)
    params = weights.make(config, seed)
    eng = kind.build_engine(config, tspec, params)
    kind.warm_up(eng, t)
    kind.capture(eng)
    tracer = bench_run.Tracer()
    lead = min(tspec["trace"]["lead_s"], seconds / 2)
    span = min(tspec["trace"]["seconds"], seconds - lead)
    client = PhaseClient(eng, t, kind, spec.loop(tspec), tracer=tracer,
                         lead_s=lead, trace_s=span)
    kind.prime(client, t)
    at_start = eng.obs.phase_seconds()
    w = client.run(seconds)
    at_end = eng.obs.phase_seconds()
    try:
        tr = load(tracer.xplane())
    finally:
        tracer.close()
    ticks = w.ticks
    counts = {k: v for k, v in eng.obs.registry.snapshot()["counters"].items()
              if k.startswith(PHASE_SPANS)}
    in_steps = sum(x.t1 - x.t0 for x in ticks)
    step_phase = sum(x.phases.get(STEP_PHASE, 0.0) for x in ticks)
    first = before_trace(ticks)
    between = [b.t0 - a.t1 for a, b in zip(first, first[1:])]
    result = {
        "cell": cell.name, "seed": seed, "device": devs[0].device_kind,
        "window_s": w.seconds, "steps": len(ticks),
        "traced_steps": sum(x.traced for x in ticks),
        "phase_ms.before_trace": phase_ms(first),
        "phase_ms.traced": phase_ms([x for x in ticks if x.traced]),
        "phase_ms.after_trace": phase_ms(
            [x for x in ticks[len(first):] if not x.traced]),
        "client": {
            "bench_step_s": in_steps,
            "serving_step_s": step_phase,
            "outside_bench_step_s": w.seconds - in_steps,
            "between_steps_ms_before_trace": (
                1e3 * statistics.median(between) if between else None),
            "serving_submit_s": (at_end.get("serving.submit", 0.0)
                                 - at_start.get("serving.submit", 0.0))},
        "stage.cnn": stage_cnn(ticks),
        "sample.decode": sample_decode(ticks),
        "kv_copy.decode": kv_copy_decode(tr),
        "scoped_share": tr.scoped_share(),
        "by_scope": tr.by_scope(),
        "breakdown": tr.breakdown(),
        "spans_per_step": (sum(counts.values()) / counts[
            f'{PHASE_SPANS}{{phase="{STEP_PHASE}"}}']),
        "span_cost_us": span_cost_us(),
    }
    if out is not None:
        out.mkdir(parents=True, exist_ok=True)
        rows = [{"t0": x.t0, "t1": x.t1, "traced": x.traced,
                 "images": x.images, "decodes": len(x.decodes),
                 "prefills": len(x.prefills), "phases": x.phases}
                for x in ticks]
        with open(out / f"{cell.name}.ticks.json", "w") as f:
            json.dump(rows, f)
        if len(tr.steps) >= extract_steps:
            with gzip.open(out / f"{cell.name}.json.gz", "wt") as f:
                json.dump(extract(tr, extract_steps), f,
                          separators=(",", ":"))
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=Path, default=None)
    args = ap.parse_args(argv)
    from bench import run as bench_run
    cell = spec.load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(REPO / "src"))
    try:
        devs = bench_run.devices(cell.chips)
    except bench_run.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    bench_run.use_compile_cache()
    result = run_phases(cell, args.seed, args.seconds, devs, args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
