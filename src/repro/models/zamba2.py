"""Zamba2-style hybrid: a Mamba2 backbone with one *shared* attention
block applied every ``shared_attn_every`` layers (weights reused at each
application — the arch's signature trick).

Mamba2 mixer per layer: in_proj -> [z | x | B | C | dt], short causal
depthwise conv over (x|B|C), selective scan (kernels/mamba2), gated
RMSNorm, out_proj.  The shared attention block is a full transformer
block (attn + MLP) with a sliding window (``attn_window``), which is
what makes the long_500k decode cell sub-quadratic (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from ..configs.base import ArchConfig
from ..core.ir import (ModelGraph, attention_node, decode_attention_node,
                       elementwise_node, embed_node, matmul_node, norm_node,
                       ssm_scan_node)
from ..core.regions import (PersistentSpec, StateCaps, kv_cache_spec,
                            register_state_family)
from ..kernels.mamba2 import mamba2_decode_step, mamba2_scan
from ..parallel.act_sharding import shard_act
from .common import ParamDef, Rotary, rms_norm
from .transformer import (_attention, _attention_decode, _attn_defs, _mlp,
                          _norm)

__all__ = ["param_defs", "forward", "init_cache", "decode_step",
           "to_graph", "to_decode_graph", "block_prefill", "block_decode"]

_CONV_K = 4


def _n_apps(cfg: ArchConfig) -> int:
    e = cfg.shared_attn_every
    if not e:          # pure-mamba2 config: no shared attention at all
        return 0
    return (cfg.n_layers + e - 1) // e


def param_defs(cfg: ArchConfig) -> dict:
    dt = cfg.jdtype
    L, D = cfg.n_layers, cfg.d_model
    di, N, H = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    conv_ch = di + 2 * N
    blocks = {
        "norm": ParamDef((L, D), ("layers", "embed"), dt, "ones"),
        "in_proj": ParamDef((L, D, 2 * di + 2 * N + H),
                            ("layers", "embed", "ff"), dt),
        "conv_w": ParamDef((L, _CONV_K, conv_ch), ("layers", None, "ff"),
                           dt, init_scale=0.5),
        "A_log": ParamDef((L, H), ("layers", None), jnp.float32, "zeros"),
        "dt_bias": ParamDef((L, H), ("layers", None), jnp.float32, "zeros"),
        "D_skip": ParamDef((L, H), ("layers", None), jnp.float32, "ones"),
        "gate_norm": ParamDef((L, di), ("layers", "ff"), dt, "ones"),
        "out_proj": ParamDef((L, di, D), ("layers", "ff", "embed"), dt),
    }
    defs = {
        "embed": ParamDef((cfg.vocab, D), ("vocab", "embed"), dt, "embed"),
        "blocks": blocks,
        "final_norm": ParamDef((D,), ("embed",), dt, "ones"),
        "lm_head": ParamDef((D, cfg.vocab), ("embed", "vocab"), dt),
    }
    if cfg.shared_attn_every:
        shared = {}
        shared["attn_norm"] = ParamDef((D,), ("embed",), dt, "ones")
        shared.update({k: ParamDef(v.shape[1:], v.axes[1:], v.dtype)
                       for k, v in _attn_defs(cfg, L).items()})
        shared["mlp_norm"] = ParamDef((D,), ("embed",), dt, "ones")
        shared["w_gate"] = ParamDef((D, cfg.d_ff), ("embed", "ff"), dt)
        shared["w_up"] = ParamDef((D, cfg.d_ff), ("embed", "ff"), dt)
        shared["w_down"] = ParamDef((cfg.d_ff, D), ("ff", "embed"), dt)
        defs["shared"] = shared
    return defs


def _mixer_dims(p) -> tuple[int, int, int, int]:
    """(d_inner, ssm_state, ssm_heads, ssm_head_dim) from the param
    shapes alone, so the executor's block entry points never consult
    the model config: A_log is (H,), gate_norm is (di,), and in_proj's
    output splits as [z(di) | x(di) | B(N) | C(N) | dt(H)]."""
    H = p["A_log"].shape[-1]
    di = p["gate_norm"].shape[-1]
    N = (p["in_proj"].shape[-1] - 2 * di - H) // 2
    return di, N, H, di // H


def _split_proj(zxbcdt, di, N):
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]
    return z, xBC, dt


def _causal_conv(xBC, conv_w):
    """Depthwise causal conv, kernel _CONV_K.  xBC (B, S, C); conv_w (K, C)."""
    K = conv_w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1]] * conv_w[i][None, None]
              for i in range(K))
    return jax.nn.silu(out.astype(jnp.float32)).astype(xBC.dtype)


def _mamba_mixer(h, p, *, impl, state=None, conv_state=None, length=None):
    """h (B, S, D) -> (out, new_ssm_state, new_conv_state).

    ``length`` marks h as right-padded (Program prefill pins
    (1, max_len)): pad rows become scan identities — dt=0 after the
    softplus makes the decay exp(A*0)=1 and the dB*x contribution 0 —
    so the returned recurrent state is exactly the state at the true
    length, and the conv taps are gathered at rows
    [length-K+1, length) instead of the block tail."""
    B, S, D = h.shape
    di, N, H, P = _mixer_dims(p)
    z, xBC, dt = _split_proj(h @ p["in_proj"], di, N)
    if conv_state is not None:      # decode: roll the conv window
        window = jnp.concatenate([conv_state, xBC], axis=1)   # (B, K-1+S, C)
        new_conv_state = window[:, -(_CONV_K - 1):]
        xBC = _causal_conv(window, p["conv_w"])[:, -S:]
    elif length is not None:
        idx = length - (_CONV_K - 1) + jnp.arange(_CONV_K - 1)
        rows = xBC[:, jnp.clip(idx, 0, S - 1)]
        new_conv_state = jnp.where((idx >= 0)[None, :, None], rows,
                                   jnp.zeros((), xBC.dtype))
        xBC = _causal_conv(xBC, p["conv_w"])
    else:
        zeros = jnp.zeros((B, _CONV_K - 1, xBC.shape[-1]), xBC.dtype)
        new_conv_state = jnp.concatenate([zeros, xBC],
                                         axis=1)[:, -(_CONV_K - 1):]
        xBC = _causal_conv(xBC, p["conv_w"])
    x, Bm, Cm = xBC[..., :di], xBC[..., di:di + N], xBC[..., di + N:]
    xh = x.reshape(B, S, H, P)
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + p["dt_bias"][None, None])          # (B,S,H)
    if length is not None:
        dtv = jnp.where((jnp.arange(S) < length)[None, :, None], dtv, 0.0)
    A = -jnp.exp(p["A_log"])
    y, h_fin = mamba2_scan(xh, dtv, A, Bm, Cm, D_skip=p["D_skip"],
                           h0=state, return_state=True, impl=impl)
    y = y.reshape(B, S, di)
    y = rms_norm(y, p["gate_norm"]) * jax.nn.silu(z.astype(jnp.float32)
                                                  ).astype(y.dtype)
    return y @ p["out_proj"], h_fin, new_conv_state


def block_prefill(h, p_i, *, impl="auto", length=None):
    """Executor entry for one ``ssm_scan`` prefill op — the whole
    mamba block (pre-norm + mixer + residual) on (B, S, D), recurrent
    state zero-initialised (prefill always restarts a slot).  Returns
    (out, (ssm (B, H, N, P) f32, conv (B, K-1, di+2N)))."""
    mixed, s_fin, c_fin = _mamba_mixer(rms_norm(h, p_i["norm"]), p_i,
                                       impl=impl, length=length)
    return shard_act(h + mixed, "hidden"), (s_fin, c_fin)


def block_decode(h, p_i, ssm_state, conv_state, *, impl="auto"):
    """Executor entry for one ``ssm_scan`` decode op: h (slots, D),
    one token per slot against the per-slot recurrent states."""
    mixed, s_new, c_new = _mamba_mixer(
        rms_norm(h, p_i["norm"])[:, None], p_i, impl=impl,
        state=ssm_state, conv_state=conv_state)
    return h + mixed[:, 0], (s_new, c_new)


def forward(params, tokens, cfg: ArchConfig, *, impl: str = "auto",
            return_cache: bool = False, cache_len: int | None = None,
            remat: bool = False, return_hidden: bool = False):
    B, S = tokens.shape
    e = cfg.shared_attn_every
    h = params["embed"][tokens].astype(cfg.jdtype)
    h = shard_act(h, "hidden")
    rot = Rotary(cfg.hd, cfg.rope_theta)
    cos, sin = rot.freqs(jnp.arange(S))
    shared = params.get("shared")

    def shared_block(x):
        if return_cache:
            a, kv = _attention(rms_norm(x, shared["attn_norm"]), shared,
                               cfg, cos, sin, impl=impl,
                               window=cfg.attn_window, return_kv=True)
        else:
            a = _attention(rms_norm(x, shared["attn_norm"]), shared, cfg,
                           cos, sin, impl=impl, window=cfg.attn_window)
            kv = None
        x = x + a
        m, _ = _mlp(rms_norm(x, shared["mlp_norm"]), shared,
                    _DenseCfg(cfg))
        return shard_act(x + m, "hidden"), kv

    def body(carry, xs):
        p_i, idx = xs
        if e:
            is_attn = idx % e == 0
            if return_cache:
                def yes(x):
                    return shared_block(x)
                def no(x):
                    KV, hd = cfg.n_kv_heads, cfg.hd
                    zero = (jnp.zeros((B, KV, S, hd), cfg.jdtype),) * 2
                    return x, zero
                carry, kv = jax.lax.cond(is_attn, yes, no, carry)
            else:
                carry = jax.lax.cond(is_attn,
                                     lambda x: shared_block(x)[0],
                                     lambda x: x, carry)
                kv = None
        else:
            kv = None
        carry, (s_fin, c_fin) = block_prefill(carry, p_i, impl=impl)
        ys = (kv, s_fin, c_fin) if return_cache else kv
        return carry, ys

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    idxs = jnp.arange(cfg.n_layers)
    h, ys = jax.lax.scan(body, h, (params["blocks"], idxs))
    h = rms_norm(h, params["final_norm"])
    logits = (None if return_hidden
              else shard_act(h @ params["lm_head"], "logits"))
    out = {"logits": logits, "aux": {}}
    if return_hidden:
        out["hidden"] = h
    if return_cache:
        kvs, ssm_stack, conv_stack = ys
        if e:
            # keep only the layers where the shared block actually ran
            app_layers = jnp.arange(0, cfg.n_layers, e)
            k_stack = kvs[0][app_layers]
            v_stack = kvs[1][app_layers]
        else:
            KV, hd = cfg.n_kv_heads, cfg.hd
            k_stack = jnp.zeros((0, B, KV, S, hd), cfg.jdtype)
            v_stack = k_stack
        cache = _prefill_cache(cfg, k_stack, v_stack, B, S)
        cache["ssm"] = ssm_stack
        cache["conv"] = conv_stack
        out["cache"] = cache
    return out


class _DenseCfg:
    """Proxy hiding MoE fields so _mlp runs the dense path."""

    def __init__(self, cfg):
        object.__setattr__(self, "_c", cfg)

    def __getattr__(self, k):
        if k == "n_experts":
            return 0
        return getattr(self._c, k)


def _prefill_cache(cfg, k_stack, v_stack, B, S):
    """Convert prefill KV (full S) into the rolling window cache."""
    W = cfg.attn_window or S
    if S >= W:
        # last W positions, laid out so slot = pos % W matches.
        idx = (jnp.arange(S - W, S)) % W
        kw = jnp.zeros(k_stack.shape[:3] + (W,) + k_stack.shape[4:],
                       k_stack.dtype)
        kw = kw.at[:, :, :, idx].set(k_stack[:, :, :, S - W:])
        vw = jnp.zeros_like(kw).at[:, :, :, idx].set(
            v_stack[:, :, :, S - W:])
    else:
        pad = W - S
        kw = jnp.pad(k_stack, ((0, 0),) * 3 + ((0, pad), (0, 0)))
        vw = jnp.pad(v_stack, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    cache = init_cache(cfg, B, W)
    kw = kw.astype(cfg.kv_jdtype)
    vw = vw.astype(cfg.kv_jdtype)
    cache.update({"attn_k": kw, "attn_v": vw,
                  "pos": jnp.full((B,), S, jnp.int32)})
    return cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    dt = cfg.jdtype
    L, di, N = cfg.n_layers, cfg.d_inner, cfg.ssm_state
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    KV, hd = cfg.n_kv_heads, cfg.hd
    W = min(max_len, cfg.attn_window) if cfg.attn_window else max_len
    napp = _n_apps(cfg)
    kdt = cfg.kv_jdtype
    return {
        "ssm": jnp.zeros((L, batch, H, N, P), jnp.float32),
        "conv": jnp.zeros((L, batch, _CONV_K - 1, di + 2 * N), dt),
        "attn_k": jnp.zeros((napp, batch, KV, W, hd), kdt),
        "attn_v": jnp.zeros((napp, batch, KV, W, hd), kdt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }


def decode_step(params, cache, tokens, cfg: ArchConfig, *,
                impl: str = "auto"):
    B = tokens.shape[0]
    e = cfg.shared_attn_every
    pos = cache["pos"]
    h = params["embed"][tokens].astype(cfg.jdtype)
    rot = Rotary(cfg.hd, cfg.rope_theta)
    cos, sin = rot.freqs(pos)
    shared = params.get("shared")

    # Shared attention applications, gathered outside the mamba scan so
    # each application indexes its own rolling KV slot.
    kc, vc = cache["attn_k"], cache["attn_v"]

    def shared_apply(x, app_idx, kc, vc):
        a_in = rms_norm(x, shared["attn_norm"])
        a, ck, cv = _attention_decode(a_in, shared, cfg, kc[app_idx],
                                      vc[app_idx], pos, cos, sin, impl=impl)
        x = x + a
        m, _ = _mlp(rms_norm(x, shared["mlp_norm"])[:, None], shared,
                    _DenseCfg(cfg))
        x = x + m[:, 0]
        return x, kc.at[app_idx].set(ck), vc.at[app_idx].set(cv)

    def body(carry, xs):
        p_i, s_i, c_i, idx = xs
        h_c, kc, vc = carry
        if e:
            def yes(args):
                h_c, kc, vc = args
                return shared_apply(h_c, idx // e, kc, vc)
            h_c, kc, vc = jax.lax.cond(idx % e == 0, yes,
                                       lambda a: a, (h_c, kc, vc))
        h_c, (s_new, c_new) = block_decode(h_c, p_i, s_i, c_i, impl=impl)
        return (h_c, kc, vc), (s_new, c_new)

    idxs = jnp.arange(cfg.n_layers)
    (h, kc, vc), (ssm_new, conv_new) = jax.lax.scan(
        body, (h, kc, vc),
        (params["blocks"], cache["ssm"], cache["conv"], idxs))
    h = rms_norm(h, params["final_norm"])
    logits = h @ params["lm_head"]
    new_cache = {"ssm": ssm_new, "conv": conv_new, "attn_k": kc,
                 "attn_v": vc, "pos": pos + 1}
    return logits, new_cache


# --- Program lowering (generic named state) ---------------------------------------
def _emit_shared_block(g, cfg, a: int, resid: str, M: int, by: int,
                       add_attention) -> str:
    """Emit one application of the shared attention block — standard
    transformer ops against the *unstacked* "shared/..." params (the
    same weights at every application; only the KV regions differ per
    application index ``a``)."""
    D, H, KV, hd, F = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                       cfg.d_ff)
    an = f"app{a}.attn_norm"
    g.add(norm_node(an, M * D, dtype_bytes=by, inputs=[resid],
                    norm="rmsnorm", param="shared/attn_norm"))
    g.add(matmul_node(f"app{a}.wq", M, D, H * hd, dtype_bytes=by,
                      inputs=[an], param="shared/wq"))
    g.add(matmul_node(f"app{a}.wk", M, D, KV * hd, dtype_bytes=by,
                      inputs=[an], param="shared/wk"))
    g.add(matmul_node(f"app{a}.wv", M, D, KV * hd, dtype_bytes=by,
                      inputs=[an], param="shared/wv"))
    add_attention(g, a, [f"app{a}.wq", f"app{a}.wk", f"app{a}.wv"])
    wo = f"app{a}.wo"
    g.add(matmul_node(wo, M, H * hd, D, dtype_bytes=by,
                      inputs=[f"app{a}.attn"], bypass_of=resid,
                      param="shared/wo"))
    mn = f"app{a}.mlp_norm"
    g.add(norm_node(mn, M * D, dtype_bytes=by, inputs=[wo],
                    norm="rmsnorm", param="shared/mlp_norm"))
    g.add(matmul_node(f"app{a}.w_gate", M, D, F, dtype_bytes=by,
                      inputs=[mn], fused_activation=cfg.activation,
                      param="shared/w_gate"))
    g.add(matmul_node(f"app{a}.w_up", M, D, F, dtype_bytes=by,
                      inputs=[mn], param="shared/w_up"))
    g.add(elementwise_node(f"app{a}.glu_mul", "mul", M * F, dtype_bytes=by,
                           inputs=[f"app{a}.w_gate", f"app{a}.w_up"]))
    g.add(matmul_node(f"app{a}.w_down", M, F, D, dtype_bytes=by,
                      inputs=[f"app{a}.glu_mul"], bypass_of=wo,
                      param="shared/w_down"))
    return f"app{a}.w_down"


def _mamba_state_names(i: int) -> tuple[str, str]:
    """Per-layer persistent state names, in ProgramOp.state_regions
    order (recurrent SSM state, conv taps)."""
    return (f"l{i}.ssm", f"l{i}.conv")


def to_graph(cfg: ArchConfig, batch: int = 1, seq: int = 64,
             dtype_bytes: int | None = None,
             write_cache: bool = False) -> ModelGraph:
    """Lower the zamba2 hybrid to the compiler IR: the shared attention
    block (every ``shared_attn_every`` layers, *before* that layer's
    mamba block) lowers fine-grained — it IS a transformer block, so it
    reuses the whole dense op vocabulary including the windowed ring KV
    plan, one pair of KV regions per application — while each mamba
    block is one coarse ``ssm_scan`` op (pre-norm + conv + selective
    scan + gated out-proj + residual) against its recurrent state."""
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    D = cfg.d_model
    e = cfg.shared_attn_every
    M = batch * seq

    def add_attention(g, a, qkv):
        cache_meta = ({"k_cache": f"app{a}.k_cache",
                       "v_cache": f"app{a}.v_cache"} if write_cache else {})
        g.add(attention_node(
            f"app{a}.attn", seq_q=seq, seq_kv=seq, heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, batch=batch,
            causal=True, dtype_bytes=by, inputs=qkv,
            window=cfg.attn_window, rope_theta=cfg.rope_theta,
            **cache_meta))

    g = ModelGraph(cfg.name)
    g.add(embed_node("embed", M, cfg.vocab, D, dtype_bytes=by,
                     param="embed"))
    resid = "embed"
    for i in range(cfg.n_layers):
        if e and i % e == 0:
            resid = _emit_shared_block(g, cfg, i // e, resid, M, by,
                                       add_attention)
        g.add(ssm_scan_node(
            f"l{i}.mamba", seq=seq, heads=cfg.ssm_heads,
            head_dim=cfg.ssm_head_dim, state=cfg.ssm_state, d_model=D,
            batch=batch, dtype_bytes=by, inputs=[resid],
            param=f"blocks:{i}",
            **({"states": _mamba_state_names(i)} if write_cache else {})))
        resid = f"l{i}.mamba"
    g.add(norm_node("final_norm", M * D, dtype_bytes=by, inputs=[resid],
                    norm="rmsnorm", param="final_norm"))
    g.add(matmul_node("lm_head", M, D, cfg.vocab, dtype_bytes=by,
                      inputs=["final_norm"], param="lm_head"))
    return g


def to_decode_graph(cfg: ArchConfig, slots: int = 8, max_len: int = 256,
                    dtype_bytes: int | None = None) -> ModelGraph:
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    D = cfg.d_model
    e = cfg.shared_attn_every
    W = min(max_len, cfg.attn_window) if cfg.attn_window else max_len

    def add_attention(g, a, qkv):
        g.add(decode_attention_node(
            f"app{a}.attn", cache_len=W, heads=cfg.n_heads,
            kv_heads=cfg.n_kv_heads, head_dim=cfg.hd, slots=slots,
            dtype_bytes=by, inputs=qkv, window=cfg.attn_window,
            rope_theta=cfg.rope_theta, k_cache=f"app{a}.k_cache",
            v_cache=f"app{a}.v_cache"))

    g = ModelGraph(cfg.name + ".decode")
    g.add(embed_node("embed", slots, cfg.vocab, D, dtype_bytes=by,
                     param="embed"))
    resid = "embed"
    for i in range(cfg.n_layers):
        if e and i % e == 0:
            resid = _emit_shared_block(g, cfg, i // e, resid, slots, by,
                                       add_attention)
        g.add(ssm_scan_node(
            f"l{i}.mamba", seq=1, heads=cfg.ssm_heads,
            head_dim=cfg.ssm_head_dim, state=cfg.ssm_state, d_model=D,
            batch=slots, dtype_bytes=by, inputs=[resid],
            param=f"blocks:{i}", states=_mamba_state_names(i),
            decode=True))
        resid = f"l{i}.mamba"
    g.add(norm_node("final_norm", slots * D, dtype_bytes=by,
                    inputs=[resid], norm="rmsnorm", param="final_norm"))
    g.add(matmul_node("lm_head", slots, D, cfg.vocab, dtype_bytes=by,
                      inputs=["final_norm"], param="lm_head"))
    return g


def _hybrid_state_specs(cfg: ArchConfig, slots: int, max_len: int):
    """Per-layer SSM recurrent state (f32, O(1) in ``max_len``) + conv
    taps, plus one ring KV pair per shared-attention *application*.
    Windowed is the only serving capability that survives the mix: the
    ring KV slides, but the recurrent state is neither pageable nor
    chunkable nor rollback-truncatable."""
    di, N = cfg.d_inner, cfg.ssm_state
    H, P = cfg.ssm_heads, cfg.ssm_head_dim
    dt = jnp.dtype(cfg.jdtype)
    kdt = jnp.dtype(cfg.kv_jdtype)
    W = min(max_len, cfg.attn_window) if cfg.attn_window else max_len
    specs = [kv_cache_spec(f"app{a}.{side}", slots, W, cfg.n_kv_heads,
                           cfg.hd, kdt)
             for a in range(_n_apps(cfg)) for side in ("k_cache", "v_cache")]
    s_shape = (slots, H, N, P)
    c_shape = (slots, _CONV_K - 1, di + 2 * N)
    for i in range(cfg.n_layers):
        ssm_name, conv_name = _mamba_state_names(i)
        specs.append(PersistentSpec(
            ssm_name, s_shape, "float32", int(np.prod(s_shape)) * 4))
        specs.append(PersistentSpec(
            conv_name, c_shape, dt.name,
            int(np.prod(c_shape)) * dt.itemsize))
    return tuple(specs), StateCaps(windowed=True)


register_state_family("hybrid", _hybrid_state_specs)
