"""bench/run.py end to end on the CPU: the look for a chip refuses a run
without a TPU; past that look (run_cell), sound runs come out correct
and runs with the timed path broken underneath come out not correct."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run, spec

DATA = spec.BENCH / "tests" / "data"
PEAK = spec.load_json(spec.BENCH / "peaks.json")["TPU v5 lite"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--workload",
         "smollm-360m.decode_batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=spec.REPO, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.fixture(scope="module")
def shared():
    return {}


def cell_run(name, shared, hook=None, controls=(), seed=2 ** 32 + 3):
    cell = spec.load_cell(name, DATA / "BENCHMARK.json", DATA)
    return run.run_cell(cell, seed, 1.0, False, jax.devices(), peak=PEAK,
                        shared=shared.setdefault(name, {}),
                        engine_hook=hook, controls=controls)


# -- faults planted in the timed path ---------------------------------------
def lm_state_unchanged(eng):
    """The decode step returns its state as it got it."""
    step = eng._decode

    def frozen(p, t, s, m=None):
        before = jax.tree.map(jnp.copy, s)      # s itself is donated
        return step(p, t, s, m)[0], before
    eng._decode = frozen


def lm_half_batch(eng):
    """Every other slot is left out of the decode step."""
    step = eng._decode
    keep = jnp.arange(eng.slots) % 2 == 0
    eng._decode = lambda p, t, s, m=None: step(p, t, s, m & keep)


def lm_token_altered(eng):
    """The third token of every request is changed where it is made."""
    pick, vocab = eng._next_token, eng.cfg.vocab
    eng._next_token = lambda req, row: (
        (pick(req, row) + 1) % vocab if len(req.out_tokens) == 2
        else pick(req, row))


def cnn_state_unchanged(eng):
    """Each batch is answered with the previous batch's logits."""
    infer, last = eng._infer, []

    def stale(p, x):
        out = infer(p, x)
        last.append(out)
        return last[-2] if len(last) > 1 else out
    eng._infer = stale


def cnn_half_batch(eng):
    """The second half of every batch is left out (zeros go in)."""
    infer = eng._infer

    def half(p, x):
        return infer(p, x.at[x.shape[0] // 2:].set(0))
    eng._infer = half


def cnn_answer_altered(eng):
    """The first answer of every batch is changed where it is made."""
    infer = eng._infer
    eng._infer = lambda p, x: infer(p, x).at[0].set(
        jnp.roll(infer(p, x)[0], 1))


@pytest.mark.parametrize("name", ["lm.backlog", "cnn.backlog"])
def test_sound_run_is_correct(name, shared):
    r = cell_run(name, shared)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"
    assert r["device"]["platform"] == "cpu"
    assert r["metrics"]["setup_s"]["value"] > 0
    assert len(r["metrics"]) >= 2


@pytest.mark.parametrize("name,fault", [
    ("lm.backlog", lm_state_unchanged), ("lm.backlog", lm_half_batch),
    ("lm.backlog", lm_token_altered),
    ("cnn.backlog", cnn_state_unchanged), ("cnn.backlog", cnn_half_batch),
    ("cnn.backlog", cnn_answer_altered),
], ids=lambda x: getattr(x, "__name__", x))
def test_broken_timed_path_is_not_correct(name, fault, shared):
    r = cell_run(name, shared, hook=fault)
    assert not r["correct"], r["compared"]


@pytest.mark.parametrize("name,number", [
    ("lm.backlog", "served_gap"), ("lm.backlog", "mean_gap"),
    ("cnn.backlog", "logit_err")])
def test_the_control_fails_the_limit(name, number, shared):
    """The reference in int8 in the program's place reads above the
    limit that the sound program keeps."""
    r = cell_run(name, shared, controls=("int8",))
    c = r["compared"]
    assert c[number]["value"] <= c[number]["limit"]
    assert c[f"control_{number}.int8"]["value"] > c[number]["limit"]
