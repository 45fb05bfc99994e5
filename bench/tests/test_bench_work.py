"""bench/work against hand counts."""
import pytest

from bench import spec
from bench.work import cnn, dense_lm

ALEX = spec.load_json(spec.BENCH / "configs" / "alexnet-owt.json")
SMOL = spec.load_json(spec.BENCH / "configs" / "smollm-360m.json")


def test_alexnet_conv_flops_per_image():
    # 2 * Ho * Wo * Cout * k * k * Cin per conv, by hand:
    hand = 2 * (55 * 55 * 64 * 11 * 11 * 3 + 27 * 27 * 192 * 5 * 5 * 64
                + 13 * 13 * 384 * 3 * 3 * 192 + 13 * 13 * 256 * 3 * 3 * 384
                + 13 * 13 * 256 * 3 * 3 * 256)
    convs = sum(f for k, f in cnn.layer_flops(ALEX) if k == "conv")
    assert convs == hand
    assert convs == pytest.approx(1.311e9, rel=1e-3)
    fcs = sum(f for k, f in cnn.layer_flops(ALEX) if k == "fc")
    assert fcs == 2 * (9216 * 4096 + 4096 * 4096 + 4096 * 1000)
    assert cnn.image_flops(ALEX) == convs + fcs


def test_alexnet_conv_calls_scale_with_the_batch():
    one, many = cnn.conv_calls(ALEX, 1), cnn.conv_calls(ALEX, 64)
    assert len(one) == 5
    assert sum(f for f, _ in many) == 64 * sum(f for f, _ in one)
    # conv1: input 224x224x3, weights 11x11x3x64 + bias, output after its
    # 3x3/2 pool 27x27x64, float32.
    assert one[0][1] == 4 * (224 * 224 * 3 + 11 * 11 * 3 * 64 + 64
                             + 27 * 27 * 64)


def test_smollm_layer_decode_token():
    c = 777
    q, kv, o = 2 * 960 * 960, 2 * 2 * 960 * 320, 2 * 960 * 960
    mlp = 3 * 2 * 960 * 2560
    attn = 2 * 2 * 15 * 64 * c              # QK^T and PV
    assert dense_lm.layer_decode_token_flops(SMOL, c) == q + kv + o + mlp + attn
    assert dense_lm.decode_token_flops(SMOL, c) == (
        32 * (q + kv + o + mlp + attn) + 2 * 960 * 49152)


def test_smollm_attention_calls():
    f, b = dense_lm.decode_attention_call(SMOL, [100, 300])
    assert f == 4 * 15 * 64 * 400
    assert b == 2 * (2 * 5 * 64 * 400 + 2 * 2 * 15 * 64)
    f, b = dense_lm.flash_attention_call(SMOL, 10)
    assert f == 4 * 15 * 64 * 55            # the causal triangle: 55 pairs
    assert b == 2 * 10 * 64 * (2 * 15 + 2 * 5)


def test_smollm_prefill_counts_one_head_row():
    n = 5
    assert dense_lm.prefill_flops(SMOL, n) == (
        32 * (n * dense_lm.layer_linear_flops(SMOL)
              + dense_lm.causal_attention_flops(SMOL, n))
        + 2 * 960 * 49152)
