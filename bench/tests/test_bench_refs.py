"""The plain references against the program at small sizes on the CPU:
the CNN Program, and the LM prefill Program then the decode Program
reading its KV cache, all on the same benchmark-made weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights
from bench.refs import cnn as cnn_ref
from bench.refs import dense_lm

DATA = spec.BENCH / "tests" / "data"

TINY_CNN = {
    "kind": "cnn", "input_hw": 32, "input_ch": 3, "n_classes": 10,
    "dtype": "float32",
    "layers": [
        {"kind": "conv", "c_out": 16, "k": 5, "stride": 2, "pad": 2,
         "activation": "relu"},
        {"kind": "maxpool", "k": 3, "stride": 2, "pad": 0},
        {"kind": "conv", "c_out": 32, "k": 3, "stride": 1, "pad": 1,
         "activation": "relu"},
        {"kind": "maxpool", "k": 3, "stride": 2, "pad": 0},
        {"kind": "fc", "c_out": 64, "activation": "relu"},
        {"kind": "fc", "c_out": 10, "activation": None},
    ]}


def highest(fn, *args):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(fn)(*args))


def test_cnn_reference_matches_the_program():
    from repro.configs.base import CNNConfig, CNNLayer
    from repro.models.cnn import compile_program
    from repro.runtime.executor import jitted_runner
    cfg = CNNConfig(name="bench-tiny", input_hw=32, input_ch=3,
                    n_classes=10, layers=tuple(
                        CNNLayer(**{k: v for k, v in l.items()})
                        for l in TINY_CNN["layers"]))
    params = weights.make(TINY_CNN, 11)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jitted_runner(compile_program(cfg, batch=2),
                                       impl="reference")(params, x))
    want = highest(lambda p, x: cnn_ref.forward(TINY_CNN, p, x), params, x)
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


def test_lm_reference_matches_prefill_then_decode_through_the_cache():
    from repro.configs import get_config
    from repro.models.transformer import compile_program_pair
    from repro.runtime import executor
    config = spec.load_json(DATA / "configs" / "smollm-360m-smoke.json")
    cfg = get_config(config["registry"])
    S, n, steps = config["max_len"], 11, 6
    params = weights.make(config, 5)
    pair = compile_program_pair(cfg, slots=2, max_len=S)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (n,), 0,
                                           config["vocab_size"]), np.int32)
    toks = np.zeros((1, S), np.int32)
    toks[0, :n] = prompt
    with jax.default_matmul_precision("highest"):
        logits, state = executor.run_prefill(
            pair.prefill, params, jnp.asarray(toks),
            executor.init_program_state(pair), 1, n, impl="reference")
        got = [np.asarray(logits[0, :n])]
        seq = list(prompt) + [int(np.argmax(got[0][-1]))]
        for _ in range(steps):
            step = np.zeros((2,), np.int32)
            step[1] = seq[-1]
            logits, state = executor.run_decode(
                pair.decode, params, jnp.asarray(step), state,
                jnp.asarray([False, True]), impl="reference")
            got.append(np.asarray(logits[1])[None])
            seq.append(int(np.argmax(got[-1][0])))
    got = np.concatenate(got)                       # (n + steps, V)
    full = np.asarray(seq[:-1], np.int32)[None]
    want = highest(lambda p, t: dense_lm.logits(
        config, p, dense_lm.hidden(config, p, t)[0]), params, full)
    assert want.shape == got.shape
    np.testing.assert_allclose(got, want, rtol=1e-4,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("quant", ["int8", "bfloat16"])
def test_the_control_precision_departs_from_the_reference(quant):
    config = spec.load_json(DATA / "configs" / "smollm-360m-smoke.json")
    params = weights.make(config, 5)
    t = jax.random.randint(jax.random.PRNGKey(2), (1, 16), 0, 256)
    ref = highest(lambda p, t: dense_lm.hidden(config, p, t), params, t)
    low = highest(lambda p, t: dense_lm.hidden(config, p, t, quant=quant),
                  params, t)
    err = np.abs(low - ref).max() / np.abs(ref).max()
    assert 1e-4 < err < 0.2
