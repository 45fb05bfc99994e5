"""Phase spans and op scopes: ``Observability.span`` accumulates and
nests, the engine's ``serving.*`` spans tile each step on both Program
paths without changing what is served, and every ProgramOp's HLO
carries its ``<kernel>.<name>`` scope under a named runner."""
import dataclasses
import glob
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.configs.base import CNNConfig, CNNLayer as C
from repro.models import cnn, init_params, transformer
from repro.obs import PHASE_SECONDS, PHASE_SPANS, Observability
from repro.runtime import executor
from repro.serving import Request, ServingEngine

K0 = jax.random.PRNGKey(0)
LM = dataclasses.replace(REGISTRY["smollm-360m"].smoke(), n_layers=2)
LM_PARAMS = init_params(transformer.param_defs(LM), K0)
CNN = CNNConfig(
    name="tiny-phases", input_hw=16, input_ch=4, n_classes=10,
    layers=(C("conv", 8, 3, 1, 1), C("maxpool", k=2, stride=2),
            C("conv", 8, 3, 1, 1), C("fc", 10, activation=None)))
CNN_PARAMS = init_params(cnn.param_defs(CNN), K0)


class _Ticks:
    """A fake clock: each read advances by the next listed step."""

    def __init__(self, *steps):
        self.t, self.steps = 0.0, list(steps)

    def __call__(self):
        self.t += self.steps.pop(0)
        return self.t


# --- the span primitive ------------------------------------------------------------

def test_span_accumulates_seconds_and_counts():
    obs = Observability(clock=_Ticks(0, 2, 10, 3))
    for _ in range(2):
        with obs.span("serving.fetch") as sp:
            pass
    assert sp.seconds == 3
    assert obs.phase_seconds() == {"serving.fetch": 5}
    c = obs.registry.snapshot()["counters"]
    assert c[f'{PHASE_SECONDS}{{phase="serving.fetch"}}'] == 5
    assert c[f'{PHASE_SPANS}{{phase="serving.fetch"}}'] == 2


def test_spans_nest():
    # reads: outer in 0, inner in 1, inner out 4, outer out 9
    obs = Observability(clock=_Ticks(0, 1, 3, 5))
    with obs.span("serving.admit") as outer:
        with obs.span("serving.prefill", uid=3) as inner:
            pass
    assert (outer.seconds, inner.seconds) == (9, 3)
    assert obs.phase_seconds() == {"serving.admit": 9,
                                   "serving.prefill": 3}


def test_obs_imports_before_jax():
    code = "import sys, repro.obs; assert 'jax' not in sys.modules"
    env = dict(os.environ, PYTHONPATH="src")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(__file__)),
                   timeout=60)


def test_span_is_a_trace_annotation_under_the_profiler(tmp_path):
    from jax.profiler import ProfileData
    obs = Observability()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.span("serving.admit"):
            with obs.span("serving.prefill", uid=7):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    with obs.span("serving.admit"):     # profiler off: counters only
        pass
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    events = {ev.name: dict(ev.stats)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    assert "serving.admit" in events
    assert events["serving.prefill"]["uid"] == 7
    assert obs.registry.snapshot()["counters"][
        f'{PHASE_SPANS}{{phase="serving.admit"}}'] == 2


# --- the engine's phases -----------------------------------------------------------

CASES = {
    "lm": dict(use_program=True),
    "lm_paged": dict(use_program=True, paged=True, page_size=8),
    "lm_chunked": dict(use_program=True, chunk_size=8),
    "cnn": {},
}
PHASES = {
    "lm": {"admit", "prefill", "dispatch", "wait", "fetch", "sample"},
    "lm_paged": {"admit", "prefill", "pages", "dispatch", "wait", "fetch",
                 "sample"},
    "lm_chunked": {"admit", "chunk", "dispatch", "wait", "fetch",
                   "sample"},
    "cnn": {"stage", "dispatch", "wait", "fetch", "sample"},
}
NESTED = {"serving.step", "serving.submit", "serving.prefill"}


class _NoSpan:
    seconds = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def _serve(case):
    """Serve a few requests, one step at a time; returns the outputs,
    each step's phase seconds and the engine's bundle."""
    rng = np.random.default_rng(5)
    if case == "cnn":
        eng = ServingEngine(CNN, CNN_PARAMS, slots=2, impl="reference")
        reqs = [Request(uid=i, prompt=rng.standard_normal(
            (16, 16, 4)).astype(np.float32)) for i in range(5)]
    else:
        eng = ServingEngine(LM, LM_PARAMS, slots=2, max_len=32,
                            impl="reference", **CASES[case])
        reqs = [Request(uid=i, prompt=rng.integers(
            1, LM.vocab, size=4 + 3 * i).astype(np.int32),
            max_new_tokens=5) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    steps = []
    while eng.live or eng.queue or eng.admission or eng._prefilling:
        before = eng.obs.phase_seconds()
        eng.step()
        after = eng.obs.phase_seconds()
        steps.append({k: v - before.get(k, 0.0) for k, v in after.items()})
    return [r.out_tokens for r in reqs], steps, eng.obs


@pytest.mark.parametrize("case", sorted(CASES))
def test_engine_phases_tile_the_step(case, monkeypatch):
    served, steps, obs = _serve(case)
    seen = {k for s in steps for k, v in s.items() if v > 0}
    assert seen == {f"serving.{p}" for p in PHASES[case]} | {
        "serving.step"}
    assert obs.phase_seconds()["serving.submit"] > 0
    step = sum(s["serving.step"] for s in steps)
    children = sum(v for s in steps for k, v in s.items()
                   if k not in NESTED)
    assert children <= step
    assert step - children <= 0.05 * step          # self time
    # observation is not intervention: the same outputs without spans
    monkeypatch.setattr(Observability, "span",
                        lambda self, name, **args: _NoSpan())
    bare, _, _ = _serve(case)
    assert served == bare


# --- op scopes and runner names ----------------------------------------------------

def _runner_args(runner):
    """(jitted runner, its Program, example arguments)."""
    if runner == "program_run":
        prog = cnn.compile_program(CNN, batch=2)
        return (executor.jitted_runner(prog, impl="reference"), prog,
                (CNN_PARAMS, jnp.zeros((2, 16, 16, 4), jnp.float32)))
    pair = transformer.compile_program_pair(LM, slots=2, max_len=32)
    state = executor.init_program_state(pair)
    i32 = jnp.int32
    if runner == "program_prefill":
        return (executor.jitted_prefill_runner(pair.prefill,
                                               impl="reference"),
                pair.prefill, (LM_PARAMS, jnp.zeros((1, 32), i32), state,
                               0, 4))
    if runner == "program_chunk":
        v = jnp.zeros((2,), i32)
        return (executor.jitted_chunk_runner(pair.prefill, impl="reference"),
                pair.prefill, (LM_PARAMS, jnp.zeros((2, 32), i32), state,
                               v, v, v + 4, v + 4, v))
    return (executor.jitted_decode_runner(pair.decode, impl="reference"),
            pair.decode, (LM_PARAMS, jnp.zeros((2,), i32), state,
                          jnp.ones((2,), bool)))


@pytest.mark.parametrize("runner", ["program_run", "program_prefill",
                                    "program_chunk", "program_decode"])
def test_every_op_carries_its_scope(runner):
    fn, prog, args = _runner_args(runner)
    hlo = fn.lower(*args).compile().as_text()
    assert hlo.startswith(f"HloModule jit_{runner},")
    for op in prog.ops:
        assert f'op_name="jit({runner})/{op.kernel}.{op.name}/' in hlo, \
            op.name
    if runner == "program_decode":
        attn = [op for op in prog.ops if op.kernel == "decode_attention"]
        assert attn and all(executor.op_scope(op).startswith(
            "decode_attention.") for op in attn)
