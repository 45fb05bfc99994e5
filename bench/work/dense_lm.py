"""Work of the dense decoder: a prefill of L real rows, a decode token
at context c, and the attention kernels' calls."""
from __future__ import annotations

import numpy as np

from bench.work import itemsize


def _dims(config: dict):
    return (config["num_hidden_layers"], config["hidden_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["intermediate_size"],
            config["vocab_size"])


def _elt(config: dict) -> int:
    return itemsize(config["torch_dtype"])


def layer_linear_flops(config: dict) -> float:
    """Projections and SwiGLU MLP of one layer, per token."""
    _, D, H, KV, hd, F, _ = _dims(config)
    return 2.0 * D * (H + 2 * KV) * hd + 2.0 * H * hd * D + 3 * 2.0 * D * F


def layer_decode_token_flops(config: dict, c: int) -> float:
    """One layer, one decode token attending to ``c`` rows."""
    _, _, H, _, hd, _, _ = _dims(config)
    return layer_linear_flops(config) + 4.0 * H * hd * c


def head_flops(config: dict) -> float:
    _, D, _, _, _, _, V = _dims(config)
    return 2.0 * D * V


def decode_token_flops(config: dict, c: int) -> float:
    L = config["num_hidden_layers"]
    return L * layer_decode_token_flops(config, c) + head_flops(config)


def causal_attention_flops(config: dict, n: int) -> float:
    """QK^T and PV over the causal triangle of ``n`` rows, one layer."""
    _, _, H, _, hd, _, _ = _dims(config)
    return 4.0 * H * hd * n * (n + 1) / 2


def prefill_flops(config: dict, n: int) -> float:
    """A prefill of ``n`` real rows; the head runs on the last row only,
    the one whose logits give the first token."""
    L = config["num_hidden_layers"]
    return (L * (n * layer_linear_flops(config)
                 + causal_attention_flops(config, n)) + head_flops(config))


def decode_attention_call(config: dict, contexts) -> tuple[float, float]:
    """(operations, least bytes) of one layer's decode attention over
    the live slots, slot s attending to ``contexts[s]`` rows: read q,
    the K and V rows up to the context, write the output."""
    _, _, H, KV, hd, _, _ = _dims(config)
    c = float(np.sum(contexts))
    n = len(contexts)
    flops = 4.0 * H * hd * c
    nbytes = _elt(config) * (2.0 * KV * hd * c + 2.0 * n * H * hd)
    return flops, nbytes


def flash_attention_call(config: dict, n: int) -> tuple[float, float]:
    """(operations, least bytes) of one layer's causal prefill attention
    over ``n`` real rows: read q, k, v, write the output."""
    _, _, H, KV, hd, _, _ = _dims(config)
    nbytes = _elt(config) * n * hd * (2.0 * H + 2 * KV)
    return causal_attention_flops(config, n), nbytes
