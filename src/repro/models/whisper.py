"""Whisper-style encoder-decoder backbone.

Per the assignment spec the modality frontend is a STUB: ``input_specs``
provides precomputed frame embeddings (B, T_enc, D) standing in for the
log-mel + conv1d stem.  The backbone is faithful: pre-LN layernorm
blocks, non-gated GELU MLPs, sinusoidal encoder positions, learned
decoder positions, tied decoder embedding head, cross-attention in every
decoder layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

import numpy as np

from ..configs.base import ArchConfig
from ..core.ir import (ModelGraph, attention_node, cross_attention_node,
                       decode_attention_node, embed_node, matmul_node,
                       norm_node)
from ..core.regions import (PersistentSpec, StateCaps, kv_cache_spec,
                            register_state_family)
from ..kernels.decode_attention import decode_attention
from ..parallel.act_sharding import shard_act
from .common import ParamDef, layer_norm
from .transformer import (_attention, _attn_defs, _heads, _mlp,
                          _write_cache)

__all__ = ["param_defs", "forward", "init_cache", "decode_step",
           "encode_memory", "to_graph", "to_decode_graph"]


def _ln_defs(cfg, L, name):
    dt = cfg.jdtype
    shape = (L, cfg.d_model) if L else (cfg.d_model,)
    axes = ("layers", "embed") if L else ("embed",)
    return {name: ParamDef(shape, axes, dt, "ones"),
            name + "_b": ParamDef(shape, axes, dt, "zeros")}


def param_defs(cfg: ArchConfig) -> dict:
    dt = cfg.jdtype
    Le, Ld = cfg.n_encoder_layers, cfg.n_layers
    enc = {}
    enc.update(_ln_defs(cfg, Le, "attn_norm"))
    enc.update(_attn_defs(cfg, Le))
    enc.update(_ln_defs(cfg, Le, "mlp_norm"))
    enc["w_gate"] = ParamDef((Le, cfg.d_model, cfg.d_ff),
                             ("layers", "embed", "ff"), dt)
    enc["w_down"] = ParamDef((Le, cfg.d_ff, cfg.d_model),
                             ("layers", "ff", "embed"), dt)
    dec = {}
    dec.update(_ln_defs(cfg, Ld, "attn_norm"))
    dec.update(_attn_defs(cfg, Ld))
    dec.update(_ln_defs(cfg, Ld, "cross_norm"))
    dec.update({"x" + k: v for k, v in _attn_defs(cfg, Ld).items()})
    dec.update(_ln_defs(cfg, Ld, "mlp_norm"))
    dec["w_gate"] = ParamDef((Ld, cfg.d_model, cfg.d_ff),
                             ("layers", "embed", "ff"), dt)
    dec["w_down"] = ParamDef((Ld, cfg.d_ff, cfg.d_model),
                             ("layers", "ff", "embed"), dt)
    defs = {
        "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                          dt, "embed"),
        "pos_embed": ParamDef((cfg.max_pos or 4096, cfg.d_model),
                              (None, "embed"), dt, "embed"),
        "enc_blocks": enc,
        "dec_blocks": dec,
    }
    defs.update(_ln_defs(cfg, None, "enc_final_norm"))
    defs.update(_ln_defs(cfg, None, "final_norm"))
    return defs


def _sinusoid(T: int, D: int) -> jax.Array:
    half = D // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / (half - 1))
    ang = jnp.arange(T)[:, None] * freqs[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


class _WhisperCfg:
    """Proxy making the shared transformer helpers use layernorm."""

    def __init__(self, cfg):
        object.__setattr__(self, "_c", cfg)

    def __getattr__(self, k):
        if k == "norm":
            return "layernorm"
        if k in ("gated_mlp",):
            return False
        if k == "activation":
            return "gelu"
        if k == "n_experts":
            return 0
        return getattr(self._c, k)


def encode(params, frames, cfg: ArchConfig, *, impl="auto"):
    """frames: (B, T_enc, D) stub embeddings -> (B, T_enc, D)."""
    c = _WhisperCfg(cfg)
    h = frames.astype(cfg.jdtype) + _sinusoid(
        frames.shape[1], cfg.d_model).astype(cfg.jdtype)[None]
    h = shard_act(h, "hidden")

    def body(carry, p_i):
        a = _attention(layer_norm(carry, p_i["attn_norm"],
                                  p_i["attn_norm_b"]),
                       p_i, c, None, None, impl=impl, causal=False)
        carry = carry + a
        m, _ = _mlp(layer_norm(carry, p_i["mlp_norm"], p_i["mlp_norm_b"]),
                    p_i, c)
        return shard_act(carry + m, "hidden"), None

    h, _ = jax.lax.scan(body, h, params["enc_blocks"])
    return layer_norm(h, params["enc_final_norm"],
                      params["enc_final_norm_b"])


def forward(params, tokens, cfg: ArchConfig, *, encoder_frames=None,
            impl: str = "auto", return_cache: bool = False,
            cache_len: int | None = None, remat: bool = False,
            return_hidden: bool = False):
    """Decoder forward given stub encoder frames."""
    assert encoder_frames is not None, "whisper needs encoder_frames"
    c = _WhisperCfg(cfg)
    enc_out = encode(params, encoder_frames, cfg, impl=impl)
    B, S = tokens.shape
    h = params["embed"][tokens].astype(cfg.jdtype)
    h = h + params["pos_embed"][:S][None].astype(cfg.jdtype)
    h = shard_act(h, "hidden")

    def body(carry, p_i):
        a, kv = _attention(layer_norm(carry, p_i["attn_norm"],
                                      p_i["attn_norm_b"]),
                           p_i, c, None, None, impl=impl, causal=True,
                           return_kv=True)
        carry = carry + a
        xp = {k[1:]: v for k, v in p_i.items() if k.startswith("x")}
        xa = _attention(layer_norm(carry, p_i["cross_norm"],
                                   p_i["cross_norm_b"]),
                        xp, c, None, None, impl=impl, causal=False,
                        kv_override=enc_out)
        carry = carry + xa
        m, _ = _mlp(layer_norm(carry, p_i["mlp_norm"], p_i["mlp_norm_b"]),
                    p_i, c)
        carry = shard_act(carry + m, "hidden")
        return carry, kv if return_cache else None

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    h, kvs = jax.lax.scan(body, h, params["dec_blocks"])
    h = layer_norm(h, params["final_norm"], params["final_norm_b"])
    logits = (None if return_hidden
              else shard_act(h @ params["embed"].T, "logits"))
    out = {"logits": logits, "aux": {}}
    if return_hidden:
        out["hidden"] = h
    if return_cache:
        k_stack, v_stack = kvs
        CL = cache_len or S
        if CL > S:
            padw = ((0, 0),) * 3 + ((0, CL - S), (0, 0))
            k_stack = jnp.pad(k_stack, padw)
            v_stack = jnp.pad(v_stack, padw)
        cache = {"k": k_stack.astype(cfg.kv_jdtype),
                 "v": v_stack.astype(cfg.kv_jdtype),
                 "pos": jnp.full((B,), S, jnp.int32)}
        xk, xv = _cross_kv(params, cfg, enc_out)
        cache["cross_k"] = xk.astype(cfg.kv_jdtype)
        cache["cross_v"] = xv.astype(cfg.kv_jdtype)
        out["cache"] = cache
    return out


def _cross_kv(params, cfg, enc_out):
    KV, hd = cfg.n_kv_heads, cfg.hd
    def one(p):
        return (_heads(enc_out @ p["xwk"], KV, hd),
                _heads(enc_out @ p["xwv"], KV, hd))
    return jax.vmap(one)(params["dec_blocks"])


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               encoder_seq: int | None = None) -> dict:
    KV, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    dt = cfg.kv_jdtype
    Te = encoder_seq or cfg.encoder_seq
    return {
        "k": jnp.zeros((L, batch, KV, max_len, hd), dt),
        "v": jnp.zeros((L, batch, KV, max_len, hd), dt),
        "cross_k": jnp.zeros((L, batch, KV, Te, hd), dt),
        "cross_v": jnp.zeros((L, batch, KV, Te, hd), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(params, cache, tokens, cfg: ArchConfig, *,
                impl: str = "auto"):
    c = _WhisperCfg(cfg)
    B = tokens.shape[0]
    pos = cache["pos"]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    h = params["embed"][tokens].astype(cfg.jdtype)
    h = h + params["pos_embed"][pos].astype(cfg.jdtype)

    def body(carry, xs):
        p_i, ck, cv, xk, xv = xs
        a_in = layer_norm(carry, p_i["attn_norm"], p_i["attn_norm_b"])
        q = (a_in @ p_i["wq"]).reshape(B, H, hd)
        k = (a_in @ p_i["wk"]).reshape(B, KV, hd)
        v = (a_in @ p_i["wv"]).reshape(B, KV, hd)
        ck, cv = _write_cache(ck, cv, k.astype(ck.dtype),
                              v.astype(cv.dtype), pos % ck.shape[2])
        a = decode_attention(q, ck, cv,
                             kv_len=jnp.minimum(pos + 1, ck.shape[2]),
                             impl=impl)
        carry = carry + a.reshape(B, H * hd) @ p_i["wo"]
        x_in = layer_norm(carry, p_i["cross_norm"], p_i["cross_norm_b"])
        xq = (x_in @ p_i["xwq"]).reshape(B, H, hd)
        xa = decode_attention(xq, xk, xv, impl=impl)
        carry = carry + xa.reshape(B, H * hd) @ p_i["xwo"]
        m, _ = _mlp(layer_norm(carry, p_i["mlp_norm"],
                               p_i["mlp_norm_b"])[:, None], p_i, c)
        return carry + m[:, 0], (ck, cv)

    h, (k_new, v_new) = jax.lax.scan(
        body, h, (params["dec_blocks"], cache["k"], cache["v"],
                  cache["cross_k"], cache["cross_v"]))
    h = layer_norm(h, params["final_norm"], params["final_norm_b"])
    logits = h @ params["embed"].T
    new_cache = dict(cache)
    new_cache.update({"k": k_new, "v": v_new, "pos": pos + 1})
    return logits, new_cache


# --- Program lowering (generic named state) ---------------------------------------
def encode_memory(params, frames, cfg: ArchConfig, *,
                  impl: str = "auto") -> dict:
    """Run the encoder once and project the per-layer cross K/V — the
    admission-time write into the decoder Program's *read-only*
    persistent memory regions.  ``frames`` is one request's (T_enc, D)
    stub embedding (or (1, T_enc, D)); returns {region name: (T_enc,
    KV, hd) row} for the engine to place at the admitted slot."""
    if frames.ndim == 2:
        frames = frames[None]
    enc_out = encode(params, frames, cfg, impl=impl)
    xk, xv = _cross_kv(params, cfg, enc_out)        # (L, 1, KV, Te, hd)
    rows = {}
    for i in range(cfg.n_layers):
        rows[f"l{i}.cross_k"] = xk[i, 0].transpose(1, 0, 2)
        rows[f"l{i}.cross_v"] = xv[i, 0].transpose(1, 0, 2)
    return rows


def to_graph(cfg: ArchConfig, batch: int = 1, seq: int = 64,
             dtype_bytes: int | None = None,
             write_cache: bool = False) -> ModelGraph:
    """Lower the whisper *decoder* to the compiler IR: pre-LN layernorm
    blocks with a causal self-attention arm (standard dense KV plan)
    and a ``cross_attention`` arm per layer reading the persistent
    encoder memory (``encode_memory`` fills it at admission — the
    encoder itself runs once per request, outside the token loop, so it
    never appears in the per-token instruction stream).  The tied head
    reuses the embedding table transposed."""
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    D, H, KV, hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff)
    Te = cfg.encoder_seq
    M = batch * seq
    g = ModelGraph(cfg.name)
    g.add(embed_node("embed", M, cfg.vocab, D, dtype_bytes=by,
                     param="embed", param_b="pos_embed"))
    resid = "embed"
    for i in range(cfg.n_layers):
        def bp(k, i=i):
            return f"dec_blocks/{k}:{i}"
        an = f"l{i}.attn_norm"
        g.add(norm_node(an, M * D, dtype_bytes=by, inputs=[resid],
                        norm="layernorm", param=bp("attn_norm"),
                        param_b=bp("attn_norm_b")))
        g.add(matmul_node(f"l{i}.wq", M, D, H * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wq")))
        g.add(matmul_node(f"l{i}.wk", M, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wk")))
        g.add(matmul_node(f"l{i}.wv", M, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wv")))
        cache_meta = ({"k_cache": f"l{i}.k_cache",
                       "v_cache": f"l{i}.v_cache"} if write_cache else {})
        g.add(attention_node(
            f"l{i}.attn", seq_q=seq, seq_kv=seq, heads=H, kv_heads=KV,
            head_dim=hd, batch=batch, causal=True, dtype_bytes=by,
            inputs=[f"l{i}.wq", f"l{i}.wk", f"l{i}.wv"], **cache_meta))
        wo = f"l{i}.wo"
        g.add(matmul_node(wo, M, H * hd, D, dtype_bytes=by,
                          inputs=[f"l{i}.attn"], bypass_of=resid,
                          param=bp("wo")))
        cn = f"l{i}.cross_norm"
        g.add(norm_node(cn, M * D, dtype_bytes=by, inputs=[wo],
                        norm="layernorm", param=bp("cross_norm"),
                        param_b=bp("cross_norm_b")))
        g.add(matmul_node(f"l{i}.xwq", M, D, H * hd, dtype_bytes=by,
                          inputs=[cn], param=bp("xwq")))
        g.add(cross_attention_node(
            f"l{i}.cross", seq_q=seq, mem_len=Te, heads=H, kv_heads=KV,
            head_dim=hd, batch=batch, k_mem=f"l{i}.cross_k",
            v_mem=f"l{i}.cross_v", dtype_bytes=by,
            inputs=[f"l{i}.xwq"]))
        xwo = f"l{i}.xwo"
        g.add(matmul_node(xwo, M, H * hd, D, dtype_bytes=by,
                          inputs=[f"l{i}.cross"], bypass_of=wo,
                          param=bp("xwo")))
        mn = f"l{i}.mlp_norm"
        g.add(norm_node(mn, M * D, dtype_bytes=by, inputs=[xwo],
                        norm="layernorm", param=bp("mlp_norm"),
                        param_b=bp("mlp_norm_b")))
        g.add(matmul_node(f"l{i}.w_gate", M, D, F, dtype_bytes=by,
                          inputs=[mn], fused_activation="gelu",
                          param=bp("w_gate")))
        g.add(matmul_node(f"l{i}.w_down", M, F, D, dtype_bytes=by,
                          inputs=[f"l{i}.w_gate"], bypass_of=xwo,
                          param=bp("w_down")))
        resid = f"l{i}.w_down"
    g.add(norm_node("final_norm", M * D, dtype_bytes=by, inputs=[resid],
                    norm="layernorm", param="final_norm",
                    param_b="final_norm_b"))
    g.add(matmul_node("lm_head", M, D, cfg.vocab, dtype_bytes=by,
                      inputs=["final_norm"], param="embed",
                      transpose_w=True))
    return g


def to_decode_graph(cfg: ArchConfig, slots: int = 8, max_len: int = 256,
                    dtype_bytes: int | None = None) -> ModelGraph:
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    D, H, KV, hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff)
    Te = cfg.encoder_seq
    g = ModelGraph(cfg.name + ".decode")
    g.add(embed_node("embed", slots, cfg.vocab, D, dtype_bytes=by,
                     param="embed", param_b="pos_embed"))
    resid = "embed"
    for i in range(cfg.n_layers):
        def bp(k, i=i):
            return f"dec_blocks/{k}:{i}"
        an = f"l{i}.attn_norm"
        g.add(norm_node(an, slots * D, dtype_bytes=by, inputs=[resid],
                        norm="layernorm", param=bp("attn_norm"),
                        param_b=bp("attn_norm_b")))
        g.add(matmul_node(f"l{i}.wq", slots, D, H * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wq")))
        g.add(matmul_node(f"l{i}.wk", slots, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wk")))
        g.add(matmul_node(f"l{i}.wv", slots, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wv")))
        g.add(decode_attention_node(
            f"l{i}.attn", cache_len=max_len, heads=H, kv_heads=KV,
            head_dim=hd, slots=slots, dtype_bytes=by,
            inputs=[f"l{i}.wq", f"l{i}.wk", f"l{i}.wv"],
            k_cache=f"l{i}.k_cache", v_cache=f"l{i}.v_cache"))
        wo = f"l{i}.wo"
        g.add(matmul_node(wo, slots, H * hd, D, dtype_bytes=by,
                          inputs=[f"l{i}.attn"], bypass_of=resid,
                          param=bp("wo")))
        cn = f"l{i}.cross_norm"
        g.add(norm_node(cn, slots * D, dtype_bytes=by, inputs=[wo],
                        norm="layernorm", param=bp("cross_norm"),
                        param_b=bp("cross_norm_b")))
        g.add(matmul_node(f"l{i}.xwq", slots, D, H * hd, dtype_bytes=by,
                          inputs=[cn], param=bp("xwq")))
        g.add(cross_attention_node(
            f"l{i}.cross", seq_q=1, mem_len=Te, heads=H, kv_heads=KV,
            head_dim=hd, batch=slots, k_mem=f"l{i}.cross_k",
            v_mem=f"l{i}.cross_v", dtype_bytes=by, decode=True,
            inputs=[f"l{i}.xwq"]))
        xwo = f"l{i}.xwo"
        g.add(matmul_node(xwo, slots, H * hd, D, dtype_bytes=by,
                          inputs=[f"l{i}.cross"], bypass_of=wo,
                          param=bp("xwo")))
        mn = f"l{i}.mlp_norm"
        g.add(norm_node(mn, slots * D, dtype_bytes=by, inputs=[xwo],
                        norm="layernorm", param=bp("mlp_norm"),
                        param_b=bp("mlp_norm_b")))
        g.add(matmul_node(f"l{i}.w_gate", slots, D, F, dtype_bytes=by,
                          inputs=[mn], fused_activation="gelu",
                          param=bp("w_gate")))
        g.add(matmul_node(f"l{i}.w_down", slots, F, D, dtype_bytes=by,
                          inputs=[f"l{i}.w_gate"], bypass_of=xwo,
                          param=bp("w_down")))
        resid = f"l{i}.w_down"
    g.add(norm_node("final_norm", slots * D, dtype_bytes=by,
                    inputs=[resid], norm="layernorm", param="final_norm",
                    param_b="final_norm_b"))
    g.add(matmul_node("lm_head", slots, D, cfg.vocab, dtype_bytes=by,
                      inputs=["final_norm"], param="embed",
                      transpose_w=True))
    return g


def _audio_state_specs(cfg: ArchConfig, slots: int, max_len: int):
    """Per-layer self-attention KV (standard dense ring) plus the
    *read-only* encoder memory pair written once at admission.  No
    serving capability survives the encoder coupling: memory rows are
    admission-bound (not pageable/speculatable) and the cross arm needs
    them before the first decoder row computes (not chunkable)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    kdt = jnp.dtype(cfg.kv_jdtype)
    shape = (slots, cfg.encoder_seq, KV, hd)
    specs = []
    for i in range(cfg.n_layers):
        specs += [kv_cache_spec(f"l{i}.{side}", slots, max_len, KV, hd, kdt)
                  for side in ("k_cache", "v_cache")]
        specs += [PersistentSpec(f"l{i}.{side}", shape, kdt.name,
                                 int(np.prod(shape)) * kdt.itemsize,
                                 read_only=True)
                  for side in ("cross_k", "cross_v")]
    return tuple(specs), StateCaps()


register_state_family("audio", _audio_state_specs)
