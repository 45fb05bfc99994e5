"""The per-layer readers of the program's phases and op scopes
(``stage.cnn``, ``sample.decode``, ``kv_copy.decode``) on hand-made
ticks and ops."""
from types import SimpleNamespace

import pytest

from bench import measure, spec
from bench import trace_reduce as tr
from bench.drive import Tick
from bench.tests.test_bench_trace_reduce import COPY, DECODE, MATMUL


def _tick(traced=False, images=0, decodes=(), **ph):
    return Tick(0.0, 1.0, images=images, decodes=list(decodes),
                traced=traced,
                phases={f"serving.{k}": v for k, v in ph.items()})


def _read(name, ticks=(), trace=None):
    run = SimpleNamespace(window=SimpleNamespace(ticks=list(ticks)),
                          trace=trace)
    return spec.metric_reader(name)(run)


def test_phase_shares_read_the_steps_before_the_trace():
    cnn = [_tick(images=64, step=0.10, stage=0.04, dispatch=0.06),
           _tick(images=64, step=0.10, stage=0.02, dispatch=0.08),
           _tick(images=64, traced=True, step=1.0, stage=0.9),
           _tick(images=64, step=0.10, stage=0.0, dispatch=0.1)]
    assert _read("stage.cnn", cnn) == pytest.approx(30.0)
    assert measure.before_trace(cnn) == cnn[:2]
    lm = [_tick(decodes=[5, 6], step=0.2, fetch=0.01, sample=0.01),
          _tick(decodes=[7], step=0.2, fetch=0.02, sample=0.03),
          _tick(step=0.6, admit=0.6, sample=0.6),     # no decode: left out
          _tick(decodes=[8], traced=True, step=1.0, sample=1.0)]
    assert _read("sample.decode", lm) == pytest.approx(10.0)
    assert measure.phase_share(lm[:2], ["serving.fetch",
                                        "serving.sample"]) == \
        pytest.approx(17.5)


def test_kv_copy_reads_scoped_copies_over_busy_time():
    ops = [(1.0, 2.0, DECODE), (2.0, 2.5, COPY), (2.5, 3.0, COPY),
           (3.0, 3.2, MATMUL), (9.0, 9.5, COPY)]      # the last: no step
    dec = "jit(program_decode)/"
    meta = {ops[0]: (dec + "decode_attention.l0.attn/pallas_call:", ""),
            ops[1]: (dec + "decode_attention.l0.attn/transpose:", ""),
            ops[2]: (dec + "matmul.l0.wo/transpose:", ""),
            ops[4]: (dec + "decode_attention.l1.attn/transpose:", "")}
    t = tr.Trace(ops, [(0.5, 4.0, "bench.step")], 1, meta)
    assert _read("kv_copy.decode", trace=t) == pytest.approx(100 * 0.5 / 2.2)
    assert t.scoped_share() == pytest.approx(100 * 2.0 / 2.2)
    assert dict(t.by_scope())["decode_attention/copy"] == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["stage.cnn", "sample.decode",
                                  "kv_copy.decode"])
def test_nothing_to_read_gives_none(name):
    if name == "kv_copy.decode":
        assert _read(name, trace=tr.Trace([], [])) is None
        assert _read(name, trace=None) is None
    else:
        assert _read(name, [_tick(traced=True, images=1, decodes=[1],
                                  step=1.0)]) is None
        assert _read(name, []) is None
