"""Plain dense decoder, written from the SmolLM / Llama description:
token embedding, then per layer RMSNorm -> causal GQA attention with
rotary position embedding (rotate-half form, theta from the config) ->
residual, RMSNorm -> SwiGLU MLP -> residual; a final RMSNorm and the
output head.  Query head h reads key/value head h // (heads / kv_heads).
Float32 throughout; the layers are one ``lax.scan`` over the stacked
parameters."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .quant import operands


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, heads, S, hd), positions 0..S-1."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mm(quant, x, w):
    a, b = operands(quant, x, (-1,), w, (0,))
    return a @ b


def hidden(config: dict, params: dict, tokens, quant: str | None = None):
    """tokens (B, S) int -> final-normed hidden states (B, S, D),
    float32.  Call under ``jax.default_matmul_precision("highest")``."""
    H, KV = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = config["rope_theta"]
    B, S = tokens.shape
    x = params["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        a = _rms(x, p["attn_norm"], eps)
        q = _mm(quant, a, p["wq"]).reshape(B, S, H, hd).transpose(0, 2, 1, 3)
        k = _mm(quant, a, p["wk"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
        v = _mm(quant, a, p["wv"]).reshape(B, S, KV, hd).transpose(0, 2, 1, 3)
        q, k = _rope(q, theta), _rope(k, theta)
        q = q.reshape(B, KV, H // KV, S, hd)
        s = jnp.einsum("bkgqd,bksd->bkgqs", q, k) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bkgqs,bksd->bkgqd", jax.nn.softmax(s, -1), v)
        o = o.reshape(B, H, S, hd).transpose(0, 2, 1, 3).reshape(B, S, H * hd)
        x = x + _mm(quant, o, p["wo"])
        m = _rms(x, p["mlp_norm"], eps)
        g = jax.nn.silu(_mm(quant, m, p["w_gate"])) * _mm(quant, m, p["w_up"])
        return x + _mm(quant, g, p["w_down"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"])
    return _rms(x, params["final_norm"], eps)


def logits(config: dict, params: dict, h, quant: str | None = None):
    """Output head over rows of ``hidden``: (N, D) -> (N, vocab)."""
    head = (params["embed"].T if config["tie_word_embeddings"]
            else params["lm_head"])
    return _mm(quant, h, head)
