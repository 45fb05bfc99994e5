"""The trace reduction on hand-made device ops and host spans, with op
names as a TPU v5e trace gives them."""
import pytest

from bench import trace_reduce as tr

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
