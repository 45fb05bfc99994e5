"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData``: the device planes
(``/device:TPU:<n>``) hold one event per operation on their "XLA Ops"
line; the host plane holds the benchmark's own spans (``bench.step``,
``bench.submit``) and the program's phases (``serving.step``,
``serving.wait``, ...), on the same clock.  Each device op's HLO
``op_name`` and module are stats of its event metadata, which
``ProfileData`` does not show; ``op_metadata`` parses them from the
serialized trace.  The executor runs every ProgramOp under
``jax.named_scope("<kernel>.<name>")``, so an op_name carries the scope
of the ProgramOp that emitted it
(``jit(program_decode)/decode_attention.l3.attn/transpose:``).  From
them:

* busy intervals: the union of the device operations' intervals;
* kernel time: the summed device time of a kernel's events.  A Pallas
  call (``custom_call_target="tpu_custom_call"``) is named by its
  ProgramOp scope: ``.../decode_attention.l3.attn/pallas_call`` is
  ``decode_attention``.  One that carries no scope is told by its call
  signature in the HLO text (``KERNELS``);
* the longest idle gaps inside the traced steps (``bench.step``), each
  labelled with the innermost span that covers it, a program phase
  where one does;
* the operations that took most device time, and device time by scope.
"""
from __future__ import annotations

import bisect
import itertools
import re
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench.", "serving.")
STEP = "bench.step"
# The stat of a device op's event metadata that carries the HLO
# instruction's op_name; its ``program_id`` stat names the HLO module.
SCOPE_STAT = "tf_op"

# Kernel -> the signature of its Pallas call in the HLO text, for calls
# with no ProgramOp scope:
#  decode_attention: (B*Hq, 1, D) out; kv_len (s32[B]) prefetched first
#  flash_attention:  (out (B*Hq, S, D), lse f32[B*Hq, 1, S]) from q, k, v
#  conv2d:           NHWC out from an NHWC input and HWIO weights
#  matmul:           (M, N) out from (M, K) and (K, N)
KERNELS = {
    "decode_attention": re.compile(
        r"= \w+\[\d+,1,\d+\]\S* custom-call\(s32\[\d+\]"),
    "flash_attention": re.compile(
        r"= \(\w+\[\d+,\d+,\d+\]\S*, f32\[\d+,1,\d+\]\S*\) custom-call\("),
    "conv2d": re.compile(
        r"= \w+\[\d+,\d+,\d+,\d+\]\S* custom-call\(\w+\[\d+,\d+,\d+,\d+\]"
        r"\S* %[^,]+, \w+\[\d+,\d+,\d+,\d+\]"),
    "matmul": re.compile(
        r"= \w+\[\d+,\d+\]\S* custom-call\(\w+\[\d+,\d+\]\S* %[^,]+, "
        r"\w+\[\d+,\d+\]"),
}
TPU_CALL = 'custom_call_target="tpu_custom_call"'
NO_META = (None, None)


@dataclass
class Trace:
    ops: list                       # (start_s, end_s, name) on the device
    spans: list                     # (start_s, end_s, name) host spans
    n_devices: int = 1
    meta: dict = field(default_factory=dict)   # op -> (op_name, module)

    def __post_init__(self):
        self.ops.sort()
        kinds: dict = {}
        self.kind_of = {}
        for op in self.ops:
            key = (op[2], self.op_name(op))
            if key not in kinds:
                kinds[key] = op_kind(*key)
            self.kind_of[op] = kinds[key]
        self._op_starts = [op[0] for op in self.ops]
        self.busy = _union([(a, b) for a, b, _ in self.ops])
        self._starts = [a for a, _ in self.busy]
        self.steps = sorted((a, b) for a, b, n in self.spans if n == STEP)

    def op_name(self, op) -> str | None:
        return self.meta.get(op, NO_META)[0]

    def scope(self, op) -> str | None:
        return scope_of(self.op_name(op))

    @property
    def window_s(self) -> float:
        """From the first traced step's start to the last one's end."""
        if not self.steps:
            return 0.0
        return self.steps[-1][1] - self.steps[0][0]

    @property
    def busy_s(self) -> float:
        """Busy seconds inside the traced window, averaged over chips."""
        if not self.steps:
            return 0.0
        a, b = self.steps[0][0], self.steps[-1][1]
        return self.busy_within(a, b) / self.n_devices

    def busy_within(self, a: float, b: float) -> float:
        i = max(0, bisect.bisect_right(self._starts, a) - 1)
        total = 0.0
        for s, e in itertools.islice(self.busy, i, None):
            if s >= b:
                break
            total += max(0.0, min(e, b) - max(s, a))
        return total

    def step_busy_s(self) -> float:
        """Busy seconds inside the traced steps."""
        return sum(self.busy_within(a, b) for a, b in self.steps)

    def step_ops(self) -> list:
        """The ops that start inside a traced step."""
        starts, out = self._op_starts, []
        for a, b in self.steps:
            out += self.ops[bisect.bisect_left(starts, a):
                            bisect.bisect_left(starts, b)]
        return out

    def kernel_seconds(self, kernel: str) -> float:
        return sum(op[1] - op[0] for op in self.ops
                   if self.kind_of[op] == kernel)

    def scoped_seconds(self, prefix: str, kinds) -> float:
        """Device seconds of the traced steps' ops of ``kinds`` whose
        scope starts with ``prefix``."""
        return sum(o[1] - o[0] for o in self.step_ops()
                   if self.kind_of[o] in kinds
                   and (self.scope(o) or "").startswith(prefix))

    def scoped_share(self) -> float | None:
        """% of the traced steps' device-busy time in ops that carry a
        ProgramOp scope."""
        busy = self.step_busy_s()
        if busy <= 0:
            return None
        scoped = _union([(o[0], o[1]) for o in self.step_ops()
                         if self.scope(o)])
        return 100.0 * sum(e - s for s, e in scoped) / busy

    def by_scope(self, top: int = 12) -> list:
        """Device seconds of the traced steps by op kind within scope
        kind (``decode_attention/copy``, ``(argument)/copy``), the layer
        index dropped."""
        by: dict = defaultdict(float)
        for o in self.step_ops():
            by[f"{scope_label(self.op_name(o))}/{self.kind_of[o]}"] += \
                o[1] - o[0]
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def idle_gaps(self, top: int = 10) -> list:
        """Longest gaps between busy intervals inside traced steps, each
        named by the innermost span covering its middle."""
        gaps = []
        for (a, b) in self.steps:
            prev = a
            i = max(0, bisect.bisect_right(self._starts, a) - 1)
            for s, e in itertools.islice(self.busy, i, None):
                if e <= a:
                    continue
                if s >= b:
                    break
                if s > prev:
                    gaps.append((prev, s))
                prev = max(prev, e)
            if prev < b:
                gaps.append((prev, b))
        out = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) / 2
            cover = [(b - a, n) for a, b, n in self.spans if a <= mid <= b]
            label = min(cover)[1] if cover else "outside bench spans"
            out.append([label, e - s])
        return out

    def top_ops(self, top: int = 10) -> list:
        by = defaultdict(float)
        for op in self.ops:
            by[self.kind_of[op]] += op[1] - op[0]
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:top]]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


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


def scope_label(op_name: str | None) -> str:
    """What ``Trace.by_scope`` files an op under: its scope's kernel,
    else ``(argument)`` for a copy of a runner argument, else
    ``(none)``."""
    scope = scope_of(op_name)
    if scope:
        return scope.split(".", 1)[0]
    if op_name and "/" not in op_name:
        return "(argument)"
    return "(none)"


def op_kind(name: str, op_name: str | None = None) -> str:
    """What an op is: the kernel of a Pallas call; the output type of
    another custom call; else the HLO opcode ('%fusion.12 = f32[8]
    fusion(...)' -> 'fusion')."""
    kernel = kernel_name(name, op_name)
    if kernel:
        return kernel
    if " = " not in name:
        return name
    rhs = name.split(" = ", 1)[1]
    m = re.match(r"(\(.*?\)|\S+) ([\w\-]+)\(", rhs)
    if not m:
        return rhs[:60]
    if m.group(2) == "custom-call":
        return f"custom-call {m.group(1)[:80]}"
    return m.group(2)


def kernel_name(name: str, op_name: str | None = None) -> str | None:
    """The kernel of a Pallas call: the ``<kernel>`` of the ProgramOp
    scope its op_name carries (``.../<kernel>.<name>/.../pallas_call``),
    else the kernel whose call signature (``KERNELS``) its HLO text
    matches."""
    if TPU_CALL not in name:
        return None
    scope = scope_of(op_name)
    if scope and op_name.rstrip(":").endswith("/pallas_call"):
        return scope.split(".", 1)[0]
    for kernel, pat in KERNELS.items():
        if pat.search(name):
            return kernel
    return None


def _xspace_class():
    """A protobuf class for the part of the profiler's ``XSpace`` that
    holds each device op's metadata.  Field numbers as in tsl's
    ``xplane.proto``; the fields left out are skipped when parsing."""
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
            if line.name == OPS_LINE:
                out[plane.name] = [meta.get(ev.metadata_id, NO_META)
                                   for ev in line.events]
    return out


def load(path: str) -> Trace:
    """The trace at ``path``: device ops with their op_name and module,
    and the ``bench.*`` and ``serving.*`` host spans."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    scopes = op_metadata(data)
    del data
    ops, spans, meta, n_dev = [], [], {}, 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            n_dev += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
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
    return Trace(ops, spans, max(n_dev, 1), meta)


# --- a recorded extract ------------------------------------------------------------
# The first few traced steps of a chip run, small enough to keep among
# the tests (bench/tests/data/traces, written by ``bench/phases.py
# --out``).

_OPCODE_END = re.compile(r"(\(.*?\)|\S+) [\w\-]+\(")


def _short(name: str) -> str:
    """An op's HLO text cut after its opcode, unless it is a Pallas
    call, whose operands tell the kernel (``KERNELS``)."""
    if TPU_CALL in name or " = " not in name:
        return name
    lhs, rhs = name.split(" = ", 1)
    m = _OPCODE_END.match(rhs)
    return f"{lhs} = {rhs[:m.end()]})" if m else name


def extract(tr: Trace, steps: int) -> dict:
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
    meta = [tr.meta.get(o, NO_META) for o in ops]
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


def from_extract(d: dict) -> Trace:
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
    return Trace(ops, spans, 1, meta)
