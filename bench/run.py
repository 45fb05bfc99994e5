#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the cell's traffic and weights from ``--seed``, builds the
program's ``ServingEngine`` for the configuration, warms up every shape
the traffic uses (one prefill and one decode tick, or one batch) and
puts the engine in the state the window starts from (an LM backlog: one
request in flight per slot, at staggered ages).  All of that goes
through the configuration's kind, ``bench/kinds/<kind>.py``.  The window
then drives the engine for ``--seconds`` with the cell's traffic
(``bench/loops/<kind>.py``).  Once it has closed, the served outputs are
compared with the plain float32 reference (the kind's ``compare``,
``bench/check.py``).  The last line of stdout is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics, read from a profiler trace of part of the window), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``compared``: each number
compared beside its limit, which also end standard error.

The run fails, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, and where the device is missing from
``bench/peaks.json``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import spec  # noqa: E402


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


@dataclass
class RunData:
    """What a metric reader (bench/metrics/<name>.py) reads."""
    cell: spec.Cell
    window: object            # drive.Window
    setup_s: float
    peak: dict                # bench/peaks.json entry of this device
    trace: object | None      # trace_reduce.Trace of the traced part


class NoChip(SystemExit):
    pass


def devices(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform "
                     f"{devs[0].platform!r}); this benchmark runs on chips")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX has "
                     f"{len(devs)}")
    return devs[:chips]


def peak_for(kind: str) -> dict:
    peaks = spec.load_json(spec.BENCH / "peaks.json")
    if kind not in peaks:
        raise NoChip(f"bench: device {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def use_compile_cache() -> None:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    the fixed ``<checkout>/.jax_cache``; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts lowerings (one per compiled or cache-loaded program)."""
    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.n += 1


class Tracer:
    """The profiler on part of the window; host Python tracing off."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = sorted(Path(self.dir).glob("**/*.xplane.pb"))
        if not found:
            raise RuntimeError(f"no .xplane.pb under {self.dir}")
        return str(found[-1])

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devs: list, *, controls: tuple = (), shared: dict | None = None,
             engine_hook=None, peak: dict | None = None) -> dict:
    """One run; returns the result object.  ``controls`` adds the
    control's readings and ``shared`` keeps the compiled Program for the
    next run in this process (bench/control.py); ``engine_hook(engine)``
    may break the engine underneath the timed path after its warm-up
    (the fault tests use it)."""
    import jax

    from bench import check, drive, traffic, weights
    config, tspec = cell.config, cell.traffic
    kind = spec.kind(config)
    phases = [("start", process_age_s())]

    def phase(name):
        phases.append((name, process_age_s()))
    t = traffic.make(tspec, config, seed)
    phase("traffic")
    params = weights.make(config, seed)
    phase("weights")
    eng = kind.build_engine(config, tspec, params,
                            None if shared is None else shared.get("program"))
    if shared is not None:
        shared["program"] = eng.program
    phase("engine")
    kind.warm_up(eng, t)
    if eng.live or len(eng.admission) or eng.queue:
        raise RuntimeError("warm-up left work in the engine")
    phase("warm-up")
    if engine_hook is not None:
        engine_hook(eng)
    captured = kind.capture(eng)
    tracer = Tracer() if trace else None
    lead, span = 0.0, 0.0
    if trace:
        lead = min(tspec["trace"]["lead_s"], seconds / 2)
        span = min(tspec["trace"]["seconds"], seconds - lead)
    client = drive.Client(eng, t, kind, spec.loop(tspec), tracer=tracer,
                          lead_s=lead, trace_s=span)
    kind.prime(client, t)
    phase("prime")
    counter = CompileCounter()
    setup_s = process_age_s()
    w = client.run(seconds)
    window_compiles = counter.n
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)
    reduced = None
    try:
        if tracer is not None:
            from bench import trace_reduce
            reduced = trace_reduce.load(tracer.xplane())
    finally:
        if tracer is not None:
            tracer.close()
    del eng, client
    gc.collect()

    if shared is not None:
        shared["window"] = w
    attempted = sum(any(w.start <= x <= w.end for x in r.times)
                    for r in w.recs.values())
    readings = kind.compare(config, params, t, w, traffic.rng_for(seed, 3),
                            tspec["check"], controls, captured)
    if shared is not None:
        shared["readings"] = readings
    ok, compared = check.verdict(readings, cell.limits)
    for name, value in readings.items():
        if name.startswith("control_"):
            compared[name] = {"value": value, "limit": None}
    run = RunData(cell, w, setup_s, peak or peak_for(devs[0].device_kind),
                  reduced)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": ok, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    result["compared"] = compared
    print("bench: set-up s " + ", ".join(
        f"{b[0]} {b[1] - a[1]:.3f}" for a, b in zip(phases, phases[1:]))
        + f" (imports and device {phases[0][1]:.3f})", file=sys.stderr)
    print(f"bench: window {w.seconds:.3f} s, {len(w.ticks)} steps, "
          f"compilations inside the window: {window_compiles}",
          file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    # libtpu's logs go under this run's TMPDIR, not a fixed /tmp path.
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(REPO / "src"))
    try:
        devs = devices(cell.chips)
        peak_for(devs[0].device_kind)
    except NoChip as e:
        print(e, file=sys.stderr)
        return 2
    use_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
