"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Read with ``jax.profiler.ProfileData``: the device planes
(``/device:TPU:<n>``) hold one event per operation on their "XLA Ops"
line; the host plane holds the benchmark's own spans (``bench.step``,
``bench.submit``, ``bench.wait``), on the same clock.  From them:

* busy intervals: the union of the device operations' intervals;
* kernel time: the summed device time of a kernel's events.  The
  trace names a Pallas call only by its HLO instruction (``%_run.388 =
  bf16[960,1,64] custom-call(s32[64] ..., ...),
  custom_call_target="tpu_custom_call"``), never by the kernel's own
  name, so a kernel is told by its call signature (``KERNELS``);
* the longest idle gaps inside the traced steps, each labelled with the
  benchmark span that covers it;
* the operations that took most device time.
"""
from __future__ import annotations

import bisect
import itertools
import re
from collections import defaultdict
from dataclasses import dataclass

OPS_LINE = "XLA Ops"
HOST_PREFIX = "bench."
STEP = "bench.step"

_A = r"\w+\[[\d,]*\]\S*"                     # one typed operand
# Kernel -> the signature of its Pallas call in the HLO text:
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


@dataclass
class Trace:
    ops: list                       # (start_s, end_s, name) on the device
    spans: list                     # (start_s, end_s, name) host spans
    n_devices: int = 1

    def __post_init__(self):
        self.ops.sort()
        self.kind_of = {n: op_kind(n) for n in {n for _, _, n in self.ops}}
        self.busy = _union([(a, b) for a, b, _ in self.ops])
        self._starts = [a for a, _ in self.busy]
        self.steps = sorted((a, b) for a, b, n in self.spans if n == STEP)

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

    def kernel_seconds(self, kernel: str) -> float:
        return sum(e - s for s, e, n in self.ops
                   if self.kind_of.get(n) == kernel)

    def idle_gaps(self, top: int = 10) -> list:
        """Longest gaps between busy intervals inside traced steps, each
        named by the innermost benchmark span covering its middle."""
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
        for s, e, n in self.ops:
            by[self.kind_of[n]] += e - s
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


def op_kind(name: str) -> str:
    """What an op is: the kernel of a Pallas call; the output type of
    another custom call; else the HLO opcode ('%fusion.12 = f32[8]
    fusion(...)' -> 'fusion')."""
    kernel = kernel_name(name)
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


def kernel_name(name: str) -> str | None:
    if TPU_CALL not in name:
        return None
    for kernel, pat in KERNELS.items():
        if pat.search(name):
            return kernel
    return None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans, n_dev = [], [], 0
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU"):
            n_dev += 1
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((s, s + ev.duration_ns * 1e-9, ev.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = ev.start_ns * 1e-9
                        spans.append((s, s + ev.duration_ns * 1e-9, ev.name))
    return Trace(ops, spans, max(n_dev, 1))
