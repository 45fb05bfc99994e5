"""Arithmetic shared by the metric readers: rates and tails of the
window, work of the traced steps, step utilization, idle share and
kernel roofline share.  A reader that finds nothing to read returns
None, never 0."""
from __future__ import annotations

import numpy as np

from bench import spec


def rate(run, per_tick) -> float | None:
    w = run.window
    ticks = w.ticks
    if not ticks or w.seconds <= 0:
        return None
    return sum(per_tick(t) for t in ticks) / w.seconds


def inter_output_ms(run, q: float) -> float | None:
    """Percentile of the gaps between consecutive outputs of a request,
    both inside the window."""
    w = run.window
    gaps = []
    for r in w.recs.values():
        ts = [x for x in r.times if w.start <= x <= w.end]
        gaps.extend(np.diff(ts))
    if not gaps:
        return None
    return 1e3 * float(np.percentile(gaps, q))


def traced_ticks(run) -> list:
    return [t for t in run.window.ticks if t.traced]


def step_mfu(run) -> float | None:
    """Operations of the work the window's steps completed over their
    summed host-clock time at the chip's peak, in %.  The traced steps
    are left out: they carry the profiler's own cost (a traced
    alexnet-owt step takes about nine times an untraced one)."""
    ticks = [t for t in run.window.ticks if not t.traced]
    wall = sum(t.t1 - t.t0 for t in ticks)
    if not ticks or wall <= 0:
        return None
    kind = spec.kind(run.cell.config)
    flops = sum(kind.tick_flops(run.cell.config, t) for t in ticks)
    return 100.0 * flops / (wall * run.peak["flops_per_s"])


def idle_share(run) -> float | None:
    """% of the traced steps' time (their host spans in the trace) in
    which no operation ran on the device."""
    tr = run.trace
    if tr is None or not tr.steps:
        return None
    total = sum(b - a for a, b in tr.steps)
    busy = sum(tr.busy_within(a, b) for a, b in tr.steps)
    return 100.0 * (total - busy) / total


def least_time(run, calls) -> float:
    """Least seconds of (operations, bytes) calls at the chip's peaks."""
    p = run.peak
    return sum(max(f / p["flops_per_s"], b / p["bytes_per_s"])
               for f, b in calls)


def roofline(run, kernel: str, calls) -> float | None:
    """% of the roofline that ``kernel`` reached on ``calls``, the work
    of the traced steps: least time over the summed device time of the
    kernel's events."""
    tr = run.trace
    if tr is None or not calls:
        return None
    spent = tr.kernel_seconds(kernel)
    if spent <= 0:
        return None
    return 100.0 * least_time(run, calls) / spent
