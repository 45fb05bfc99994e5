"""The trace reduction on hand-made device ops and host spans, with op
names as a TPU v5e trace gives them, and on a recorded TPU v5e trace of
smollm-360m decode ticks: program spans and op scopes are kept, a
Pallas call is named by its ProgramOp scope, and every number the
benchmark read before scopes were kept reads the same."""
import gzip
import json
from types import SimpleNamespace

import pytest

from bench import measure, spec
from bench import trace_reduce as tr
from bench.drive import Tick

CONV = ('%_run.9 = f32[8,13,13,192]{3,2,1,0:T(8,128)} custom-call('
        'f32[8,31,36,64]{3,2,1,0:T(8,128)} %pad.16, f32[5,5,64,192]'
        '{3,2,1,0:T(8,128)} %params__layer_02____w__.1, f32[1,192]{1,0} '
        '%bitcast.20), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={}')
DECODE = ('%_run.388 = bf16[960,1,64]{2,1,0:T(2,128)(2,1)} custom-call('
          's32[64]{0:T(128)} %get-tuple-element.2533, bf16[960,1,64]{2,1,0} '
          '%pad), custom_call_target="tpu_custom_call"')
FLASH = ('%_run.3 = (bf16[15,2048,64]{2,1,0}, f32[15,1,2048]{2,1,0}) '
         'custom-call(bf16[15,2048,64]{2,1,0} %a, bf16[5,2048,64]{2,1,0} %b, '
         'bf16[5,2048,64]{2,1,0} %c), custom_call_target="tpu_custom_call"')
MATMUL = ('%_run.13 = f32[128,4096]{1,0:T(8,128)} custom-call(f32[128,9216]'
          '{1,0} %pad.1, f32[9216,4096]{1,0} %w, f32[1,4096]{1,0} %b), '
          'custom_call_target="tpu_custom_call"')
COPY = ('%copy.6 = f32[64,57,58,48]{3,2,1,0:T(8,128)} copy(f32[64,57,58,48]'
        '{2,0,3,1:T(8,128)S(1)} %bitcast.14)')
# A Pallas call that matches no signature of KERNELS.
SCAN = ('%_run.5 = (bf16[4,64,128]{2,1,0}, f32[4,8,64,128]{3,2,1,0}) '
        'custom-call(bf16[4,64,128]{2,1,0} %x, f32[4,8]{1,0} %a), '
        'custom_call_target="tpu_custom_call"')

OPS = [(1.0, 2.0, CONV), (1.5, 2.5, COPY), (3.0, 3.5, CONV),
       (9.0, 9.5, MATMUL)]
BENCH_SPANS = [(0.5, 4.0, "bench.step"), (3.6, 3.9, "bench.submit"),
               (5.0, 6.0, "bench.step")]
SERVING_SPANS = [(0.8, 3.5, "serving.step"), (0.8, 1.0, "serving.stage"),
                 (1.0, 3.2, "serving.wait"), (3.2, 3.5, "serving.sample"),
                 (5.1, 5.9, "serving.step"), (5.1, 5.8, "serving.stage")]

RECORDED = spec.BENCH / "tests" / "data" / "traces" / \
    "smollm-360m.decode_batch.json.gz"
SMOL = spec.load_json(spec.BENCH / "configs" / "smollm-360m.json")
PEAK = spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"]


def test_kernels_told_by_their_call_signature():
    assert tr.op_kind(CONV) == "conv2d"
    assert tr.op_kind(DECODE) == "decode_attention"
    assert tr.op_kind(FLASH) == "flash_attention"
    assert tr.op_kind(MATMUL) == "matmul"
    assert tr.op_kind(COPY) == "copy"
    assert tr.kernel_name(COPY) is None


def test_busy_idle_and_gaps():
    ops = [(1.0, 2.0, CONV), (1.5, 2.5, COPY), (3.0, 3.5, CONV),
           (9.0, 9.5, MATMUL)]                  # the last is outside steps
    spans = [(0.5, 4.0, "bench.step"), (3.6, 3.9, "bench.submit"),
             (5.0, 6.0, "bench.step")]
    t = tr.Trace(ops, spans)
    assert t.steps == [(0.5, 4.0), (5.0, 6.0)]
    assert t.window_s == pytest.approx(5.5)
    assert t.busy_within(0.5, 4.0) == pytest.approx(2.0)   # 1-2.5, 3-3.5
    assert t.busy_s == pytest.approx(2.0)
    assert t.kernel_seconds("conv2d") == pytest.approx(1.5)
    assert t.kernel_seconds("matmul") == pytest.approx(0.5)
    gaps = t.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx([1.0, 0.5, 0.5, 0.5])
    assert gaps[0][0] == "bench.step"
    # the gap 3.5-4.0 has its middle inside bench.submit
    assert ["bench.submit", pytest.approx(0.5)] in gaps
    ops_top = dict(t.top_ops())
    assert ops_top["conv2d"] == pytest.approx(1.5)
    assert ops_top["copy"] == pytest.approx(1.0)


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
    assert tr.scope_of(op_name) == scope


def test_scoped_pallas_call_is_named_by_its_scope():
    """A kernel that no signature of KERNELS knows is named by the
    ProgramOp scope of its Pallas call, and its roofline is read with
    no entry anywhere; a scope beats a signature; an op of the scope
    that is no Pallas call keeps its opcode."""
    scan = "jit(program_decode)/ssm_scan.l0.mixer/pallas_call:"
    assert tr.kernel_name(SCAN) is None
    assert tr.op_kind(SCAN, scan) == "ssm_scan"
    assert tr.op_kind(MATMUL, "jit(program_run)/ssm_scan.l0.in_proj/"
                      "jit(matmul)/pallas_call") == "ssm_scan"
    assert tr.op_kind(COPY, "jit(program_decode)/ssm_scan.l0.mixer/"
                      "copy:") == "copy"
    assert tr.op_kind(MATMUL, "state[3]:") == "matmul"
    ops = [(1.0, 1.5, SCAN), (1.5, 2.0, COPY), (2.0, 2.25, SCAN)]
    meta = {ops[0]: (scan, "jit_program_decode"),
            ops[1]: ("jit(program_decode)/ssm_scan.l0.mixer/copy:",
                     "jit_program_decode"),
            ops[2]: (scan, "jit_program_decode")}
    t = tr.Trace(list(ops), [(0.5, 2.5, "bench.step")], 1, meta)
    assert t.kernel_seconds("ssm_scan") == pytest.approx(0.75)
    assert dict(t.top_ops())["ssm_scan"] == pytest.approx(0.75)
    assert dict(t.by_scope())["ssm_scan/ssm_scan"] == pytest.approx(0.75)
    run = SimpleNamespace(trace=t, peak={"flops_per_s": 1e12,
                                         "bytes_per_s": 1e9})
    # 0.3 s at the bytes' peak of 0.75 s spent: 40%
    assert measure.roofline(run, "ssm_scan", [(1e9, 3e8)]) == \
        pytest.approx(40.0)
    assert measure.roofline(run, "wkv", [(1e9, 3e8)]) is None


def test_program_spans_leave_the_benchmark_numbers_alone():
    """On the same ops, a trace with the program's spans reads the
    benchmark's steps, busy time, kernel time and top ops; gaps that
    only bench spans cover keep their bench labels."""
    plain = tr.Trace(list(OPS), list(BENCH_SPANS))
    scoped = tr.Trace(list(OPS), BENCH_SPANS + SERVING_SPANS)
    assert scoped.steps == plain.steps
    assert scoped.window_s == plain.window_s
    assert scoped.busy_s == plain.busy_s
    for kernel in ("conv2d", "matmul"):
        assert scoped.kernel_seconds(kernel) == plain.kernel_seconds(kernel)
    assert scoped.top_ops() == plain.top_ops()
    # 0.5-1.0 has its middle before serving.step starts, 3.5-4.0 after
    # it ends
    assert ["bench.step", pytest.approx(0.5)] in scoped.idle_gaps()
    assert ["bench.submit", pytest.approx(0.5)] in scoped.idle_gaps()


def test_gaps_take_the_innermost_program_phase():
    scoped = tr.Trace(list(OPS), BENCH_SPANS + SERVING_SPANS)
    gaps = scoped.idle_gaps()
    assert gaps[0] == ["serving.stage", pytest.approx(1.0)]   # 5.0-6.0
    assert ["serving.wait", pytest.approx(0.5)] in gaps       # 2.5-3.0


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return tr.from_extract(json.load(f))


def test_recorded_trace_finds_the_kernel_and_scoped_copies(recorded):
    """Three decode ticks of smollm-360m (32 layers) on a TPU v5e,
    before the KV cache rested lane-dense."""
    assert len(recorded.steps) == 3
    kernel = [o for o in recorded.ops
              if recorded.kind_of[o] == "decode_attention"]
    assert len(kernel) == 3 * 32
    assert all(recorded.scope(o).startswith("decode_attention.l")
               for o in kernel)
    assert {m for _, m in recorded.meta.values()} == {"jit_program_decode"}
    kv_copy = spec.metric_reader("kv_copy.decode")
    assert 10.0 < kv_copy(SimpleNamespace(trace=recorded)) < 50.0
    by = dict(recorded.by_scope(top=100))
    assert by["decode_attention/copy"] > 0
    # The rest of the KV copies are XLA's layout copies of the cache
    # arguments (op_name "state[i]:"), which no op scope can carry.
    assert by["(argument)/copy"] > 0
    assert 50.0 < recorded.scoped_share() < 100.0
    scoped_or_argument = tr._union(
        [(o[0], o[1]) for o in recorded.step_ops()
         if tr.scope_label(recorded.op_name(o)) != "(none)"])
    assert sum(e - s for s, e in scoped_or_argument) \
        >= 0.95 * recorded.step_busy_s()


def test_recorded_trace_labels_gaps_with_program_phases(recorded):
    gaps = recorded.idle_gaps()
    assert len(gaps) == 10
    assert sum(label.startswith("serving.") for label, _ in gaps) >= 8
    plain = tr.Trace(list(recorded.ops),
                     [s for s in recorded.spans
                      if s[2].startswith("bench.")])
    assert plain.busy_s == recorded.busy_s
    assert plain.top_ops() == recorded.top_ops()


@pytest.mark.parametrize("kernel,ms", [("decode_attention", 100.73),
                                       ("matmul", 5.88)])
def test_recorded_kernel_by_scope_is_kernel_by_signature(recorded, kernel,
                                                         ms):
    """Every Pallas call of the recorded ticks carries its ProgramOp
    scope; naming it by the scope gives the seconds its signature
    gives."""
    calls = [o for o in recorded.ops if tr.TPU_CALL in o[2]]
    assert calls and all(recorded.scope(o) for o in calls)
    by_signature = tr.Trace(list(recorded.ops), list(recorded.spans))
    assert recorded.kernel_seconds(kernel) == pytest.approx(ms * 1e-3,
                                                            abs=5e-6)
    assert recorded.kernel_seconds(kernel) == \
        by_signature.kernel_seconds(kernel)


# Read from this extract by the reduction that told kernels by their
# signatures alone and kept only bench.* spans.
SIGNATURE_READINGS = {
    "decode_attention": 0.10073478700000014,
    "matmul": 0.005881772999999932,
    "busy_s": 0.37203771399999974, "window_s": 0.39592933500000005,
    "decode_attention_roofline": 2.3020509824934163,
    "idle.decode": 5.976083927865539,
    "top_ops": [["copy", 0.1840104469999982],
                ["decode_attention", 0.10073478700000014],
                ["while", 0.06057090300000015],
                ["fusion", 0.03946383700000258],
                ["dynamic-update-slice", 0.020005468000000023],
                ["copy-done", 0.00912226900000002],
                ["pad", 0.006998592000000513],
                ["matmul", 0.005881772999999932],
                ["slice", 0.005089581999996685],
                ["reduce", 0.0002860879999999656]],
}


def test_recorded_trace_reads_as_before(recorded):
    """The per-layer readers of the trace read what they read when
    kernels were told by their signatures alone (three traced ticks of
    64 decodes each, at contexts 200-263, 207-270 and 214-277)."""
    want = SIGNATURE_READINGS
    ticks = [Tick(0.0, 1.0, decodes=list(range(200 + 7 * i, 264 + 7 * i)),
                  traced=True) for i in range(3)]
    run = SimpleNamespace(
        cell=SimpleNamespace(config=SMOL, traffic={"slots": 64}),
        window=SimpleNamespace(ticks=ticks), peak=PEAK, trace=recorded)
    for kernel in ("decode_attention", "matmul"):
        assert recorded.kernel_seconds(kernel) == \
            pytest.approx(want[kernel], rel=1e-12)
    assert recorded.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert recorded.window_s == pytest.approx(want["window_s"], rel=1e-12)
    for name in ("decode_attention_roofline", "idle.decode"):
        assert spec.metric_reader(name)(run) == \
            pytest.approx(want[name], rel=1e-12)
    top = recorded.top_ops()
    assert [k for k, _ in top] == [k for k, _ in want["top_ops"]]
    assert [v for _, v in top] == \
        pytest.approx([v for _, v in want["top_ops"]], rel=1e-12)


def test_op_metadata_reads_op_name_and_module():
    """A device plane as a TPU trace lays it out: the op_name is the
    ``tf_op`` stat of the event's metadata (a string, or a reference
    to an interned one), the module is named by ``program_id``."""
    xs = tr._xspace_class()()
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
    got = tr.op_metadata(xs.SerializeToString())
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
    t = tr.load(str(path))
    assert sorted(n for _, _, n in t.spans) == [
        "bench.step", "serving.step", "serving.wait"]
    assert len(t.steps) == 1
