"""bench/phases.py: the trace reduction keeps program spans and op
scopes and reads as the benchmark's does on the same trace; the phase
shares on hand-made ticks and ops; and a recorded TPU v5e trace of
smollm-360m decode ticks."""
import gzip
import json

import pytest

from bench import phases, spec
from bench import trace_reduce as tr
from bench.drive import Tick
from bench.tests.test_bench_trace_reduce import CONV, COPY, DECODE, MATMUL

RECORDED = spec.BENCH / "tests" / "data" / "traces" / \
    "smollm-360m.decode_batch.json.gz"

OPS = [(1.0, 2.0, CONV), (1.5, 2.5, COPY), (3.0, 3.5, CONV),
       (9.0, 9.5, MATMUL)]
BENCH_SPANS = [(0.5, 4.0, "bench.step"), (3.6, 3.9, "bench.submit"),
               (5.0, 6.0, "bench.step")]
SERVING_SPANS = [(0.8, 3.5, "serving.step"), (0.8, 1.0, "serving.stage"),
                 (1.0, 3.2, "serving.wait"), (3.2, 3.5, "serving.sample"),
                 (5.1, 5.9, "serving.step"), (5.1, 5.8, "serving.stage")]


@pytest.mark.parametrize("op_name,scope", [
    ("jit(program_decode)/decode_attention.l3.attn/transpose:",
     "decode_attention.l3.attn"),
    ("jit(program_decode)/matmul.lm_head/jit(matmul)/pallas_call",
     "matmul.lm_head"),
    ("jit(program_run)/conv2d.conv_00/pad", "conv2d.conv_00"),
    ("jit(program_decode)/jit(_where)/select_n:", None),
    ("state[27]:", None), ("", None), (None, None),
])
def test_scope_of(op_name, scope):
    assert phases.scope_of(op_name) == scope


def test_program_spans_leave_the_benchmark_numbers_alone():
    """On the same ops, the scoped trace reads the benchmark's steps,
    busy time, kernel time and top ops; gaps that only bench spans
    cover keep their bench labels."""
    plain = tr.Trace(list(OPS), list(BENCH_SPANS))
    scoped = phases.ScopedTrace(list(OPS), BENCH_SPANS + SERVING_SPANS)
    assert scoped.steps == plain.steps
    assert scoped.window_s == plain.window_s
    assert scoped.busy_s == plain.busy_s
    for kernel in ("conv2d", "matmul"):
        assert scoped.kernel_seconds(kernel) == plain.kernel_seconds(kernel)
    assert scoped.top_ops() == plain.top_ops()
    assert phases.ScopedTrace(list(OPS), list(BENCH_SPANS)).idle_gaps() \
        == plain.idle_gaps()
    # 0.5-1.0 has its middle before serving.step starts, 3.5-4.0 after
    # it ends
    assert ["bench.step", pytest.approx(0.5)] in scoped.idle_gaps()
    assert ["bench.submit", pytest.approx(0.5)] in scoped.idle_gaps()


def test_gaps_take_the_innermost_program_phase():
    scoped = phases.ScopedTrace(list(OPS), BENCH_SPANS + SERVING_SPANS)
    gaps = scoped.idle_gaps()
    assert gaps[0] == ["serving.stage", pytest.approx(1.0)]   # 5.0-6.0
    assert ["serving.wait", pytest.approx(0.5)] in gaps       # 2.5-3.0


def _tick(traced=False, images=0, decodes=(), **ph):
    t = Tick(0.0, 1.0, images=images, decodes=list(decodes),
             traced=traced)
    t.phases = {f"serving.{k}": v for k, v in ph.items()}
    return t


def test_phase_shares_read_the_steps_before_the_trace():
    cnn = [_tick(images=64, step=0.10, stage=0.04, dispatch=0.06),
           _tick(images=64, step=0.10, stage=0.02, dispatch=0.08),
           _tick(images=64, traced=True, step=1.0, stage=0.9),
           _tick(images=64, step=0.10, stage=0.0, dispatch=0.1)]
    assert phases.stage_cnn(cnn) == pytest.approx(30.0)
    assert phases.before_trace(cnn) == cnn[:2]
    lm = [_tick(decodes=[5, 6], step=0.2, fetch=0.01, sample=0.01),
          _tick(decodes=[7], step=0.2, fetch=0.02, sample=0.0),
          _tick(step=0.6, admit=0.6),            # no decode: left out
          _tick(decodes=[8], traced=True, step=1.0, fetch=1.0)]
    assert phases.sample_decode(lm) == pytest.approx(10.0)
    ms = phases.phase_ms(cnn[:2])
    assert ms["serving.stage"] == pytest.approx(30.0)
    assert ms["serving.step"] == pytest.approx(100.0)


def test_kv_copy_reads_scoped_copies_over_busy_time():
    ops = [(1.0, 2.0, DECODE), (2.0, 2.5, COPY), (2.5, 3.0, COPY),
           (3.0, 3.2, MATMUL), (9.0, 9.5, COPY)]      # the last: no step
    dec = "jit(program_decode)/"
    meta = {ops[0]: (dec + "decode_attention.l0.attn/pallas_call:", ""),
            ops[1]: (dec + "decode_attention.l0.attn/transpose:", ""),
            ops[2]: (dec + "matmul.l0.wo/transpose:", ""),
            ops[4]: (dec + "decode_attention.l1.attn/transpose:", "")}
    t = phases.ScopedTrace(ops, [(0.5, 4.0, "bench.step")], 1, meta)
    assert phases.kv_copy_decode(t) == pytest.approx(100 * 0.5 / 2.2)
    assert t.scoped_share() == pytest.approx(100 * 2.0 / 2.2)
    assert dict(t.by_scope())["decode_attention/copy"] == pytest.approx(0.5)


@pytest.mark.parametrize("reader", ["stage_cnn", "sample_decode",
                                    "kv_copy_decode"])
def test_nothing_to_read_gives_none(reader):
    arg = (phases.ScopedTrace([], []) if reader == "kv_copy_decode"
           else [_tick(traced=True, images=1, decodes=[1], step=1.0)])
    assert getattr(phases, reader)(arg) is None
    if reader != "kv_copy_decode":
        assert getattr(phases, reader)([]) is None
    else:
        assert phases.kv_copy_decode(None) is None


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return phases.from_extract(json.load(f))


def test_recorded_trace_finds_the_kernel_and_scoped_copies(recorded):
    """Three decode ticks of smollm-360m (32 layers) on a TPU v5e."""
    assert len(recorded.steps) == 3
    kernel = [o for o in recorded.ops
              if recorded.kind_of[o[2]] == "decode_attention"]
    assert len(kernel) == 3 * 32
    assert all(recorded.scope(o).startswith("decode_attention.l")
               for o in kernel)
    assert {m for _, m in recorded.meta.values()} == {"jit_program_decode"}
    assert 10.0 < phases.kv_copy_decode(recorded) < 50.0
    by = dict(recorded.by_scope(top=100))
    assert by["decode_attention/copy"] > 0
    # The rest of the KV copies are XLA's layout copies of the cache
    # arguments (op_name "state[i]:"), which no op scope can carry.
    assert by["(argument)/copy"] > 0
    assert 50.0 < recorded.scoped_share() < 100.0
    scoped_or_argument = tr._union(
        [(o[0], o[1]) for o in recorded.step_ops()
         if phases._scope_label(recorded.meta[o][0]) != "(none)"])
    assert sum(e - s for s, e in scoped_or_argument) \
        >= 0.95 * recorded.step_busy_s()


def test_recorded_trace_labels_gaps_with_program_phases(recorded):
    gaps = recorded.idle_gaps()
    assert len(gaps) == 10
    assert sum(label.startswith("serving.") for label, _ in gaps) >= 8
    plain = tr.Trace(list(recorded.ops),
                     [s for s in recorded.spans
                      if s[2].startswith(tr.HOST_PREFIX)])
    assert plain.busy_s == recorded.busy_s
    assert plain.top_ops() == recorded.top_ops()


def test_op_metadata_reads_op_name_and_module():
    """A device plane as a TPU trace lays it out: the op_name is the
    ``tf_op`` stat of the event's metadata (a string, or a reference
    to an interned one), the module is named by ``program_id``."""
    xs = phases._xspace_class()()
    plane = xs.planes.add(name="/device:TPU:0")
    for key, name in [(1, "tf_op"), (2, "program_id"),
                      (3, "jit(program_decode)/matmul.lm_head/dot:")]:
        e = plane.stat_metadata.add(key=key)
        e.value.id, e.value.name = key, name
    for key, name, stats in [
            (10, "jit_program_decode(7)", []),
            (11, "%copy.1 = f32[2] copy(f32[2] %a)",
             [dict(metadata_id=1, str_value="state[3]:"),
              dict(metadata_id=2, uint64_value=7)]),
            (12, "%fusion.2 = f32[2] fusion(f32[2] %b)",
             [dict(metadata_id=1, ref_value=3),
              dict(metadata_id=2, uint64_value=7)])]:
        e = plane.event_metadata.add(key=key)
        e.value.id, e.value.name = key, name
        for st in stats:
            e.value.stats.add(**st)
    line = plane.lines.add(name="XLA Ops")
    for key in (11, 12, 11, 99):
        line.events.add(metadata_id=key)
    got = phases.op_metadata(xs.SerializeToString())
    assert got == {"/device:TPU:0": [
        ("state[3]:", "jit_program_decode"),
        ("jit(program_decode)/matmul.lm_head/dot:", "jit_program_decode"),
        ("state[3]:", "jit_program_decode"), (None, None)]}


def test_load_keeps_program_spans(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation
    from repro.obs import Observability
    obs = Observability()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("bench.step"):
            with obs.span("serving.step"):
                with obs.span("serving.wait"):
                    jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    t = phases.load(str(path))
    assert sorted(n for _, _, n in t.spans) == [
        "bench.step", "serving.step", "serving.wait"]
    assert len(t.steps) == 1
