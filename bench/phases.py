#!/usr/bin/env python3
"""Put a cell's time down to the program's own phases and ops.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        [--out DIR]

Runs the cell as ``bench/run.py --trace 1`` does (``run.run_cell``)
and reports what the program says about itself there:

* per step, the seconds of each ``serving.*`` phase (``Tick.phases``,
  read by the client around each step, outside its host span);
* from the profiler trace (``trace_reduce.load``), the host spans
  ``bench.*`` and ``serving.*`` and each device op's ProgramOp scope,
  ``<kernel>.<name>``.

It prints one JSON object: whether the run came out correct, each
phase's mean milliseconds per step before, during and after the trace,
the client's time outside ``serving.step``, the per-layer metrics
``stage.cnn``, ``sample.decode`` and ``kv_copy.decode``
(``bench/metrics``), the share of the traced device-busy time in scoped
ops, device time by op scope, the longest idle gaps labelled with the
innermost span, the spans a step takes and the host cost of one span
with the profiler off.  With ``--out DIR`` it also writes every step's
phases (``<cell>.ticks.json``) and an extract of the first three traced
steps (``<cell>.json.gz``, ``trace_reduce.extract``), the form
``bench/tests/data/traces`` holds.
"""
from __future__ import annotations

import argparse
import gzip
import json
import os
import statistics
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import measure, spec, trace_reduce  # noqa: E402

READERS = ("stage.cnn", "sample.decode", "kv_copy.decode")


def phase_ms(ticks) -> dict:
    """{phase: mean ms per step} over ``ticks``."""
    if not ticks:
        return {}
    names = sorted({n for t in ticks for n in t.phases})
    return {n: 1e3 * sum(t.phases.get(n, 0.0) for t in ticks) / len(ticks)
            for n in names}


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


def run_phases(cell, seed: int, seconds: float, devs, out: Path | None,
               extract_steps: int = 3, peak: dict | None = None) -> dict:
    from bench import run as bench_run
    from repro.obs import PHASE_SPANS
    shared: dict = {}
    result = bench_run.run_cell(cell, seed, seconds, True, devs,
                                shared=shared, peak=peak)
    run = shared["run"]
    w, tr = run.window, run.trace
    ticks = w.ticks
    first = measure.before_trace(ticks)
    in_steps = sum(x.t1 - x.t0 for x in ticks)
    between = [b.t0 - a.t1 for a, b in zip(first, first[1:])]
    spans = sum(v for x in ticks for k, v in x.counters.items()
                if k.startswith(PHASE_SPANS))
    report = {
        "cell": cell.name, "seed": seed, "device": devs[0].device_kind,
        "correct": result["correct"],
        "window_s": w.seconds, "steps": len(ticks),
        "traced_steps": sum(x.traced for x in ticks),
        "phase_ms.before_trace": phase_ms(first),
        "phase_ms.traced": phase_ms([x for x in ticks if x.traced]),
        "phase_ms.after_trace": phase_ms(
            [x for x in ticks[len(first):] if not x.traced]),
        "client": {
            "bench_step_s": in_steps,
            "serving_step_s": sum(x.phases.get(measure.STEP_PHASE, 0.0)
                                  for x in ticks),
            "outside_bench_step_s": w.seconds - in_steps,
            "between_steps_ms_before_trace": (
                1e3 * statistics.median(between) if between else None)},
        **{name: spec.metric_reader(name)(run) for name in READERS},
        "scoped_share": tr.scoped_share(),
        "by_scope": tr.by_scope(),
        "breakdown": tr.breakdown(),
        "spans_per_step": spans / len(ticks) if ticks else None,
        "span_cost_us": span_cost_us(),
    }
    if out is not None:
        out.mkdir(parents=True, exist_ok=True)
        rows = [{"t0": x.t0, "t1": x.t1, "traced": x.traced,
                 "images": x.images, "decodes": len(x.decodes),
                 "prefills": len(x.prefills), "phases": x.phases,
                 "counters": x.counters}
                for x in ticks]
        with open(out / f"{cell.name}.ticks.json", "w") as f:
            json.dump(rows, f)
        if len(tr.steps) >= extract_steps:
            with gzip.open(out / f"{cell.name}.json.gz", "wt") as f:
                json.dump(trace_reduce.extract(tr, extract_steps), f,
                          separators=(",", ":"))
    return report


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
    report = run_phases(cell, args.seed, args.seconds, devs, args.out)
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
