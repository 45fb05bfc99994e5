"""Decoder-only transformer LM: dense (llama-family), MoE, and
cross-attention (VLM) variants — one implementation parameterized by
``ArchConfig``.

Layers are stacked on a leading "layers" axis and run under
``jax.lax.scan`` (one-block HLO; tractable 512-device dry-run compiles).
All matmul-shaped compute routes through kernels/ (schedule-driven
Pallas on TPU, reference on CPU); attention through the flash /
decode_attention kernels.

The dense family additionally lowers to the compiler pipeline exactly
like the CNNs (models/cnn.py): ``to_graph`` emits the layer graph
(embed -> N x {norm, qkv matmuls, flash attention, o-proj, MLP matmul
chain} -> final norm -> lm head) with the residual adds fused into the
o-/down-projection writebacks, ``compile_program`` runs it through
graph -> schedule -> regions -> Program, and ``program_forward``
executes the instruction stream through runtime/executor.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from ..core.hw import TPU_V5E, HardwareModel
from ..core.ir import (ModelGraph, attention_node, decode_attention_node,
                       elementwise_node, embed_node, matmul_node, moe_node,
                       norm_node)
from ..core.program import Program, ProgramPair, lower_to_program
from ..core.regions import (PAGE_TABLE_REGION, PersistentSpec, StateCaps,
                            allocate_regions, extend_with_persistent,
                            kv_cache_spec, paged_kv_specs,
                            register_state_family, state_specs)
from ..core.schedule import compile_model
from ..kernels.decode_attention import (decode_attention, ring_kv_len,
                                        ring_positions)
from ..kernels.flash_attention import flash_attention
from ..kernels.common import apply_activation, resolve_impl
from ..parallel.act_sharding import per_shard, shard_act
from .common import (ParamDef, Rotary, apply_rope, layer_norm, rms_norm)
from .moe import moe_mlp

__all__ = ["param_defs", "forward", "init_cache", "decode_step",
           "to_graph", "to_decode_graph", "compile_program",
           "compile_program_pair", "compile_draft_pair",
           "program_forward", "kv_cache_len"]


# --- parameter declaration -------------------------------------------------------
def _norm_defs(cfg: ArchConfig, L: int | None, name: str) -> dict:
    """Norm params; nonparametric LN (OLMo) contributes none."""
    if cfg.norm == "nonparametric":
        return {}
    dt = cfg.jdtype
    shape = (L, cfg.d_model) if L else (cfg.d_model,)
    axes = ("layers", "embed") if L else ("embed",)
    d = {name: ParamDef(shape, axes, dt, "ones")}
    if cfg.norm == "layernorm":
        d[name + "_b"] = ParamDef(shape, axes, dt, "zeros")
    return d


def _attn_defs(cfg: ArchConfig, L: int | None) -> dict:
    dt = cfg.jdtype
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    def p(shape, axes):
        if L:
            return ParamDef((L,) + shape, ("layers",) + axes, dt)
        return ParamDef(shape, axes, dt)
    return {
        "wq": p((D, H * hd), ("embed", "heads")),
        "wk": p((D, KV * hd), ("embed", "kv_heads")),
        "wv": p((D, KV * hd), ("embed", "kv_heads")),
        "wo": p((H * hd, D), ("heads", "embed")),
    }


def _mlp_defs(cfg: ArchConfig, L: int | None, moe: bool | None = None) -> dict:
    dt = cfg.jdtype
    D, F = cfg.d_model, cfg.d_ff
    def p(shape, axes):
        if L:
            return ParamDef((L,) + shape, ("layers",) + axes, dt)
        return ParamDef(shape, axes, dt)
    use_moe = cfg.n_experts > 0 if moe is None else moe
    if use_moe:
        E = cfg.n_experts
        d = {"router": p((D, E), ("embed", None)),
             "w_gate": p((E, D, F), ("experts", "embed", "ff")),
             "w_down": p((E, F, D), ("experts", "ff", "embed"))}
        if cfg.gated_mlp:
            d["w_up"] = p((E, D, F), ("experts", "embed", "ff"))
        return d
    d = {"w_gate": p((D, F), ("embed", "ff")),
         "w_down": p((F, D), ("ff", "embed"))}
    if cfg.gated_mlp:
        d["w_up"] = p((D, F), ("embed", "ff"))
    return d


def _block_defs(cfg: ArchConfig, L: int, moe: bool | None = None) -> dict:
    blocks = {}
    blocks.update(_norm_defs(cfg, L, "attn_norm"))
    blocks.update(_attn_defs(cfg, L))
    blocks.update(_norm_defs(cfg, L, "mlp_norm"))
    blocks.update(_mlp_defs(cfg, L, moe))
    return blocks


def param_defs(cfg: ArchConfig) -> dict:
    dt = cfg.jdtype
    L = cfg.n_layers
    interleaved = cfg.n_experts > 0 and cfg.moe_every > 1
    if interleaved:
        assert L % cfg.moe_every == 0, (L, cfg.moe_every)
        G = L // cfg.moe_every
        defs: dict[str, Any] = {
            "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                              dt, "embed"),
            "blocks": _block_defs(cfg, L - G, moe=False),
            "moe_blocks": _block_defs(cfg, G, moe=True),
        }
    else:
        defs = {
            "embed": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                              dt, "embed"),
            "blocks": _block_defs(cfg, L),
        }
    defs.update(_norm_defs(cfg, None, "final_norm"))
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((cfg.d_model, cfg.vocab),
                                   ("embed", "vocab"), dt)
    if cfg.cross_attn_every:
        G = cfg.n_layers // cfg.cross_attn_every
        cross = {}
        cross.update(_norm_defs(cfg, G, "attn_norm"))
        cross.update({k: ParamDef((G,) + v.shape[1:], v.axes, v.dtype)
                      for k, v in _attn_defs(cfg, L).items()})
        cross["gate"] = ParamDef((G,), ("layers",), dt, "zeros")
        defs["cross_blocks"] = cross
    return defs


# --- building blocks --------------------------------------------------------------
def _norm(h, p, cfg, name):
    if cfg.norm == "nonparametric":
        return layer_norm(h)
    if cfg.norm == "layernorm":
        return layer_norm(h, p[name], p.get(name + "_b"))
    return rms_norm(h, p[name])


def _heads(x, n, hd):
    B, S = x.shape[0], x.shape[1]
    return x.reshape(B, S, n, hd).transpose(0, 2, 1, 3)   # (B, n, S, hd)


def _attention(h, p, cfg, cos, sin, *, impl, causal=True, window=None,
               kv_override=None, return_kv=False):
    """Self- (or cross-, via kv_override) attention on (B, S, D)."""
    B, S, D = h.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = _heads(h @ p["wq"], H, hd)
    if kv_override is None:
        k = _heads(h @ p["wk"], KV, hd)
        v = _heads(h @ p["wv"], KV, hd)
        if cos is not None:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    else:
        src = kv_override                                  # (B, Skv, D)
        k = _heads(src @ p["wk"], KV, hd)
        v = _heads(src @ p["wv"], KV, hd)
        if cos is not None:
            q = apply_rope(q, cos, sin)
    q = shard_act(q, "attn_q")
    # Under sequence parallelism K/V must be whole before the chunked
    # attention scan: one small (GQA) all-gather per layer here instead
    # of a full-score all-reduce per kv chunk (§Perf H2 iter 2).
    k = shard_act(k, "attn_kv")
    v = shard_act(v, "attn_kv")
    attend = functools.partial(flash_attention, causal=causal, window=window,
                               impl=impl)
    if resolve_impl(impl) == "reference":
        out = attend(q, k, v)
    else:   # a Pallas kernel on a mesh runs per shard
        out = per_shard(attend, (q, "attn_q"), (k, "attn_kv"), (v, "attn_kv"))
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = out @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def _mlp(h, p, cfg):
    B, S, D = h.shape
    if "router" in p:
        out, aux = moe_mlp(h.reshape(B * S, D), p["router"], p["w_gate"],
                           p.get("w_up", p["w_gate"]), p["w_down"],
                           top_k=cfg.top_k,
                           capacity_factor=cfg.capacity_factor,
                           activation=cfg.activation, gated=cfg.gated_mlp)
        return out.reshape(B, S, D), aux
    g = apply_activation(h @ p["w_gate"], cfg.activation)
    if cfg.gated_mlp:
        g = g * (h @ p["w_up"])
    return g @ p["w_down"], {}


def _block(h, p, cfg, cos, sin, *, impl, window=None, return_kv=False):
    attn_in = _norm(h, p, cfg, "attn_norm")
    if return_kv:
        a, kv = _attention(attn_in, p, cfg, cos, sin, impl=impl,
                           window=window, return_kv=True)
    else:
        a = _attention(attn_in, p, cfg, cos, sin, impl=impl, window=window)
        kv = None
    h = shard_act(h + a, "hidden")
    m, aux = _mlp(_norm(h, p, cfg, "mlp_norm"), p, cfg)
    h = shard_act(h + m, "hidden")
    return (h, kv, aux) if return_kv else (h, aux)


def _cross_block(h, p, cfg, vis, *, impl):
    """Gated cross-attention sub-block (llama-3.2-vision style)."""
    a = _attention(_norm(h, p, cfg, "attn_norm"), p, cfg, None, None,
                   impl=impl, causal=False, kv_override=vis)
    return shard_act(h + jnp.tanh(p["gate"]).astype(h.dtype) * a, "hidden")


# --- forward ----------------------------------------------------------------------
def forward(params, tokens, cfg: ArchConfig, *, vision_embeds=None,
            impl: str = "auto", return_cache: bool = False,
            cache_len: int | None = None, remat: bool = False,
            return_hidden: bool = False):
    """tokens (B, S) -> {"logits": (B, S, V), "aux": {...}[, "cache"]}."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(cfg.jdtype)
    h = shard_act(h, "hidden")
    rot = Rotary(cfg.hd, cfg.rope_theta)
    cos, sin = rot.freqs(jnp.arange(S))

    def body(carry, p_i):
        out = _block(carry, p_i, cfg, cos, sin, impl=impl,
                     window=cfg.attn_window, return_kv=return_cache)
        if return_cache:
            h2, kv, aux = out
            return h2, (kv, aux)
        h2, aux = out
        return h2, aux

    if remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    interleaved = cfg.n_experts > 0 and cfg.moe_every > 1
    if cfg.cross_attn_every:
        G = cfg.n_layers // cfg.cross_attn_every
        per = cfg.cross_attn_every
        blocks = jax.tree.map(
            lambda x: x.reshape((G, per) + x.shape[1:]), params["blocks"])
        vis = vision_embeds
        assert vis is not None, "vlm arch requires vision_embeds"

        def group(carry, xs):
            cross_p, self_p = xs
            carry = _cross_block(carry, cross_p, cfg, vis, impl=impl)
            carry, ys = jax.lax.scan(body, carry, self_p)
            return carry, ys

        h, ys = jax.lax.scan(group, h, (params["cross_blocks"], blocks))
        ys = jax.tree.map(lambda x: x.reshape((cfg.n_layers,) + x.shape[2:]),
                          ys)
        kvs, auxs = ys if return_cache else (None, ys)
    elif interleaved:
        # llama4-style: (moe_every - 1) dense layers, then one MoE layer.
        per = cfg.moe_every
        G = cfg.n_layers // per
        dense = jax.tree.map(
            lambda x: x.reshape((G, per - 1) + x.shape[1:]),
            params["blocks"])

        def group(carry, xs):
            moe_p, dense_g = xs
            carry, ys_d = jax.lax.scan(body, carry, dense_g)
            carry, ys_m = body(carry, moe_p)
            return carry, (ys_d, ys_m)

        h, (ys_d, ys_m) = jax.lax.scan(group, h,
                                       (params["moe_blocks"], dense))
        if return_cache:
            (kv_d, aux_d), (kv_m, aux_m) = ys_d, ys_m
            kvs = jax.tree.map(
                lambda d, m: jnp.concatenate(
                    [d, m[:, None]], axis=1).reshape(
                        (cfg.n_layers,) + d.shape[2:]), kv_d, kv_m)
            auxs = aux_m
        else:
            kvs, auxs = None, ys_m
    else:
        h, ys = jax.lax.scan(body, h, params["blocks"])
        kvs, auxs = ys if return_cache else (None, ys)
    h = _norm(h, params, cfg, "final_norm")
    if return_hidden:
        logits = None
    else:
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = shard_act(h @ head, "logits")

    aux = {}
    if cfg.n_experts and auxs:
        aux = {k: jnp.mean(v) for k, v in auxs.items() if v is not None}
    out = {"logits": logits, "aux": aux}
    if return_hidden:
        out["hidden"] = h
    if return_cache:
        k_stack, v_stack = kvs               # (L, B, KV, S, hd)
        CL = cache_len or S
        if cfg.attn_window:
            CL = min(CL, cfg.attn_window)
        if CL > S:                           # room to append during decode
            padw = ((0, 0),) * 3 + ((0, CL - S), (0, 0))
            k_stack = jnp.pad(k_stack, padw)
            v_stack = jnp.pad(v_stack, padw)
        elif CL < S:                         # rolling window: keep last CL
            # One shared ring-layout rule (kernels/decode_attention):
            # slot j holds the latest position p < S with p % CL == j —
            # the same conversion the Program prefill performs at a
            # runtime length (executor._write_prefill_cache).
            pos = ring_positions(S, CL, S)
            k_stack = k_stack[:, :, :, pos]
            v_stack = v_stack[:, :, :, pos]
        k_stack = k_stack.astype(cfg.kv_jdtype)
        v_stack = v_stack.astype(cfg.kv_jdtype)
        cache = {"k": k_stack, "v": v_stack,
                 "pos": jnp.full((B,), S, jnp.int32)}
        if cfg.cross_attn_every:
            cache["cross_k"], cache["cross_v"] = _cross_kv(params, cfg,
                                                           vision_embeds)
        out["cache"] = cache
    return out


def _cross_kv(params, cfg, vis):
    """Precompute cross-attention KV for all cross blocks (decode)."""
    KV, hd = cfg.n_kv_heads, cfg.hd
    def one(p):
        return _heads(vis @ p["wk"], KV, hd), _heads(vis @ p["wv"], KV, hd)
    return jax.vmap(one)(params["cross_blocks"])   # (G, B, KV, Tv, hd)


# --- compile-to-Program lowering (dense family) -----------------------------------
def _require_dense(cfg: ArchConfig) -> None:
    """Gate what the *transformer-graph* lowering cannot express, with
    every blocker named (the serving engine's legacy-fallback warning
    and ``serve.py --program``'s exit-2 path print the full list).
    Dense and MoE decoder-only configs lower here; SSM / hybrid / audio
    families lower through their own modules' graph builders, so the
    remaining blockers are the vision-bridge features."""
    blockers = []
    if cfg.family not in ("dense", "moe"):
        blockers.append(f"family={cfg.family} (not a decoder-only "
                        f"transformer graph)")
    if cfg.cross_attn_every:
        blockers.append("gated cross-attention (vision bridge)")
    if cfg.n_vision_tokens:
        blockers.append("vision-encoder inputs")
    if cfg.n_encoder_layers:
        blockers.append("encoder-decoder")
    if cfg.shared_attn_every:
        blockers.append("shared attention blocks")
    if blockers:
        raise NotImplementedError(
            f"Program lowering covers the decoder-only transformer "
            f"families (windowed attention and MoE included); "
            f"{cfg.name} is blocked by: {', '.join(blockers)} — it "
            f"still runs the scan forward")


def kv_cache_len(cfg: ArchConfig, max_len: int) -> int:
    """Per-slot KV rows the §5.1 region plan reserves: the paper's
    "region sized at the largest output it holds" discipline applied to
    state — a sliding window means positions older than ``attn_window``
    are never attendable, so the persistent region holds
    ``min(max_len, attn_window)`` rows and eviction is the rolling
    overwrite at ``pos % cache_len``.  One rule shared by
    ``init_cache`` (legacy loop) and ``_kv_cache_specs`` (Programs)."""
    if cfg.attn_window:
        return min(max_len, cfg.attn_window)
    return max_len


def _block_path(cfg: ArchConfig, i: int) -> tuple[str, int, bool]:
    """(param group, index-within-group, is_moe) for global layer ``i``
    — the graph-side mirror of ``forward``'s interleaved llama4-style
    grouping ((moe_every - 1) dense layers then one MoE layer, params
    split across "blocks" / "moe_blocks") and of the all-MoE layout
    (moe_every <= 1: every layer's experts live stacked in "blocks")."""
    if cfg.n_experts > 0 and cfg.moe_every > 1:
        g, r = divmod(i, cfg.moe_every)
        if r == cfg.moe_every - 1:
            return "moe_blocks", g, True
        return "blocks", g * (cfg.moe_every - 1) + r, False
    return "blocks", i, cfg.n_experts > 0


def _build_lm_graph(cfg: ArchConfig, name: str, M: int, by: int,
                    add_attention) -> ModelGraph:
    """One block emitter for every dense-LM graph flavor (stateless,
    cache-writing prefill, per-token decode) — the flavors differ only
    in the token count M and the attention node, supplied by
    ``add_attention(g, i, qkv_inputs)``.  Keeping a single emitter is
    what guarantees the prefill and decode graphs of a serving pair can
    never structurally drift apart."""
    D, H, KV, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff

    def norm_meta(param: str | None) -> dict:
        meta = {"norm": cfg.norm}
        if cfg.norm != "nonparametric" and param is not None:
            meta["param"] = param
            if cfg.norm == "layernorm":
                meta["param_b"] = (param + "_b" if ":" not in param else
                                   param.replace(":", "_b:", 1))
        return meta

    g = ModelGraph(name)
    g.add(embed_node("embed", M, cfg.vocab, D, dtype_bytes=by,
                     param="embed"))
    resid = "embed"
    for i in range(cfg.n_layers):
        grp, gi, is_moe = _block_path(cfg, i)

        def bp(k: str, grp=grp, gi=gi) -> str:
            return f"{grp}/{k}:{gi}"
        an = f"l{i}.attn_norm"
        g.add(norm_node(an, M * D, dtype_bytes=by, inputs=[resid],
                        **norm_meta(bp("attn_norm"))))
        g.add(matmul_node(f"l{i}.wq", M, D, H * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wq")))
        g.add(matmul_node(f"l{i}.wk", M, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wk")))
        g.add(matmul_node(f"l{i}.wv", M, D, KV * hd, dtype_bytes=by,
                          inputs=[an], param=bp("wv")))
        add_attention(g, i, [f"l{i}.wq", f"l{i}.wk", f"l{i}.wv"])
        wo = f"l{i}.wo"
        g.add(matmul_node(wo, M, H * hd, D, dtype_bytes=by,
                          inputs=[f"l{i}.attn"], bypass_of=resid,
                          param=bp("wo")))
        mn = f"l{i}.mlp_norm"
        g.add(norm_node(mn, M * D, dtype_bytes=by, inputs=[wo],
                        **norm_meta(bp("mlp_norm"))))
        if is_moe:
            # One capacity-bucketed dispatch op replaces the dense MLP
            # chain; the whole block's stacked params ride the group
            # path ("moe_blocks:2" tree-slices every leaf at index 2)
            # and the routing config travels on the node for op_cfg.
            g.add(moe_node(f"l{i}.moe", tokens=M, d_model=D, d_ff=F,
                           experts=cfg.n_experts, top_k=cfg.top_k,
                           dtype_bytes=by, inputs=[mn], bypass_of=wo,
                           param=f"{grp}:{gi}",
                           capacity_factor=cfg.capacity_factor,
                           activation=cfg.activation,
                           gated=cfg.gated_mlp))
            resid = f"l{i}.moe"
            continue
        g.add(matmul_node(f"l{i}.w_gate", M, D, F, dtype_bytes=by,
                          inputs=[mn], fused_activation=cfg.activation,
                          param=bp("w_gate")))
        if cfg.gated_mlp:
            g.add(matmul_node(f"l{i}.w_up", M, D, F, dtype_bytes=by,
                              inputs=[mn], param=bp("w_up")))
            g.add(elementwise_node(f"l{i}.glu_mul", "mul", M * F,
                                   dtype_bytes=by,
                                   inputs=[f"l{i}.w_gate", f"l{i}.w_up"]))
            down_in = f"l{i}.glu_mul"
        else:
            down_in = f"l{i}.w_gate"
        g.add(matmul_node(f"l{i}.w_down", M, F, D, dtype_bytes=by,
                          inputs=[down_in], bypass_of=wo,
                          param=bp("w_down")))
        resid = f"l{i}.w_down"
    g.add(norm_node("final_norm", M * D, dtype_bytes=by, inputs=[resid],
                    **norm_meta("final_norm")))
    g.add(matmul_node("lm_head", M, D, cfg.vocab, dtype_bytes=by,
                      inputs=["final_norm"],
                      param="embed" if cfg.tie_embeddings else "lm_head",
                      transpose_w=cfg.tie_embeddings))
    return g


def _paged_cache_meta(i: int, page_size: int, kv_quant: str | None) -> dict:
    """Attention-node meta for the paged region plan: the cache names
    resolve to the §5.1 page *pools*, the shared table and (for int8
    pools) the per-page scale regions ride along, and ``page_size``
    reaches the schedule so the decode kv block is pinned to the page."""
    meta = {"k_cache": f"l{i}.k_pages", "v_cache": f"l{i}.v_pages",
            "page_table": PAGE_TABLE_REGION, "page_size": page_size}
    if kv_quant == "int8":
        meta["k_scale"] = f"l{i}.k_scale"
        meta["v_scale"] = f"l{i}.v_scale"
    return meta


def to_graph(cfg: ArchConfig, batch: int = 1, seq: int = 64,
             dtype_bytes: int | None = None,
             write_cache: bool = False,
             page_size: int | None = None,
             kv_quant: str | None = None) -> ModelGraph:
    """Lower a dense-transformer config to the compiler IR (§5.1
    steps 1-2), mirroring ``forward``'s op-for-op structure:

        embed -> N x [attn_norm, wq|wk|wv, flash_attention, wo(+resid),
                      mlp_norm, w_gate|w_up, mul, w_down(+resid)]
              -> final_norm -> lm_head

    Residual adds are not standalone ops: each block's two adds ride
    the o-projection / down-projection writeback (``bypass_of``, the
    paper's VMOV-on-writeback), which is what makes the residual stream
    a RESIDUAL_SOURCE the §5.1 allocator pins across the block.  Param
    paths point into the stacked parameter tree ("blocks/wq:3").

    ``write_cache=True`` emits the *prefill* flavor of the graph (the
    serving pair's first half): each attention node additionally names
    the persistent ``l{i}.k_cache`` / ``l{i}.v_cache`` regions it
    writes the computed (post-RoPE) K and raw V into at the admitted
    slot — a runtime operand carried by the executor's ProgramState.
    ``page_size`` switches those names to the paged plan's page pools
    (plus the shared page-table region, and per-page scale regions when
    ``kv_quant="int8"``) — see ``regions.paged_kv_specs``."""
    _require_dense(cfg)
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def add_attention(g, i, qkv):
        cache_meta = {}
        if write_cache:
            cache_meta = ({"k_cache": f"l{i}.k_cache",
                           "v_cache": f"l{i}.v_cache"} if page_size is None
                          else _paged_cache_meta(i, page_size, kv_quant))
        g.add(attention_node(
            f"l{i}.attn", seq_q=seq, seq_kv=seq, heads=H, kv_heads=KV,
            head_dim=hd, batch=batch, causal=True, dtype_bytes=by,
            inputs=qkv, window=cfg.attn_window, rope_theta=cfg.rope_theta,
            **cache_meta))

    return _build_lm_graph(cfg, cfg.name, batch * seq, by, add_attention)


def _tuned_context(cfg_name: str, batch: int, hw: HardwareModel,
                   generation: str):
    """(tuned_view, cost_model) from the active autotune cache, or
    (None, None).  Shared by the compile entry points below; the
    ``generation`` threaded through their memo keys is what makes
    re-tuning invalidate memoized Programs (the stale-Program bugfix)."""
    from ..core import autotune
    cache = autotune.active()
    if cache is None or generation == "empty":
        return None, None
    fp = autotune.hw_fingerprint(hw)
    return cache.view(cfg_name, fp, batch), cache.cost_model(fp)


def compile_program(cfg: ArchConfig, batch: int = 1, seq: int = 64,
                    hw: HardwareModel = TPU_V5E) -> Program:
    """graph -> schedule -> regions -> Program for a dense-transformer
    config, cached per (config, batch, seq, hw, tuned-cache
    generation).  Every tiling / attention-block / fusion decision in
    the returned Program comes from ``compile_model`` — the single
    source of truth, exactly as for the CNNs
    (models/cnn.py::compile_program)."""
    from ..core import autotune
    return _compile_program(cfg, batch, seq, hw,
                            autotune.active_generation())


@functools.lru_cache(maxsize=64)
def _compile_program(cfg: ArchConfig, batch: int, seq: int,
                     hw: HardwareModel, generation: str) -> Program:
    tuned, cost_model = _tuned_context(cfg.name, batch, hw, generation)
    graph = to_graph(cfg, batch=batch, seq=seq)
    schedule = compile_model(graph, hw, tuned=tuned, cost_model=cost_model)
    return lower_to_program(graph, schedule)


def to_decode_graph(cfg: ArchConfig, slots: int = 8, max_len: int = 256,
                    dtype_bytes: int | None = None,
                    page_size: int | None = None,
                    kv_quant: str | None = None) -> ModelGraph:
    """Lower the per-token decode step to the compiler IR: the same
    block structure as ``to_graph`` (one shared emitter) but with one
    token per slot (M = slots) and the attention replaced by
    ``decode_attention`` against the persistent per-block KV-cache
    regions — op-for-op the graph of ``decode_step``.

    Windowed attention lowers as a *region-plan decision*: the decode
    node's cache extent is ``kv_cache_len`` (= min(max_len,
    attn_window)), the node carries ``window`` so the schedule's
    decode-regime block chooser sizes ``block_kv`` against the window,
    and the executor's rolling write at ``pos % cache_len`` is the
    eviction — op-for-op the legacy ``_attention_decode`` ring rule."""
    _require_dense(cfg)
    by = (dtype_bytes if dtype_bytes is not None
          else jnp.dtype(cfg.jdtype).itemsize)
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    cache_len = kv_cache_len(cfg, max_len)

    def add_attention(g, i, qkv):
        if page_size is None:
            cache_meta = {"k_cache": f"l{i}.k_cache",
                          "v_cache": f"l{i}.v_cache"}
        else:
            cache_meta = _paged_cache_meta(i, page_size, kv_quant)
        g.add(decode_attention_node(
            f"l{i}.attn", cache_len=cache_len, heads=H, kv_heads=KV,
            head_dim=hd, slots=slots, dtype_bytes=by, inputs=qkv,
            window=cfg.attn_window, rope_theta=cfg.rope_theta,
            **cache_meta))

    return _build_lm_graph(cfg, cfg.name + ".decode", slots, by,
                           add_attention)


def _kv_cache_specs(cfg: ArchConfig, slots: int,
                    max_len: int) -> tuple[PersistentSpec, ...]:
    """One persistent KV region of kv_cache_len rows per block and cache
    side, in the engine's KV dtype and the lane-dense layout of
    ``regions.kv_cache_spec``.  A sliding window shrinks the resident
    rows to the window (max_len/W fewer persistent KV bytes), the §5.1
    sizing rule applied to state."""
    rows = kv_cache_len(cfg, max_len)
    return tuple(
        kv_cache_spec(f"l{i}.{side}", slots, rows, cfg.n_kv_heads, cfg.hd,
                      cfg.kv_jdtype)
        for i in range(cfg.n_layers) for side in ("k_cache", "v_cache"))


# Generic named-state hooks (regions.state_specs).  Dense KV state
# composes with every serving feature; MoE shares the KV-shaped state
# but chunked prefill is gated (expert capacity is a whole-sequence
# decision — a chunk boundary re-buckets routing) and so is
# speculation (rollback re-runs routing over rolled-back tokens).
register_state_family(
    "dense", lambda cfg, slots, max_len: (
        _kv_cache_specs(cfg, slots, max_len),
        StateCaps(paged=True, windowed=True, chunkable=True,
                  speculatable=True)))
register_state_family(
    "moe", lambda cfg, slots, max_len: (
        _kv_cache_specs(cfg, slots, max_len),
        StateCaps(paged=True, windowed=True, chunkable=False,
                  speculatable=False)))


def compile_program_pair(cfg: ArchConfig, slots: int = 8,
                         max_len: int = 256,
                         hw: HardwareModel = TPU_V5E, *,
                         paged: bool = False, page_size: int = 16,
                         page_pool: int | None = None,
                         kv_quant: str | None = None) -> ProgramPair:
    from ..core import autotune
    return _compile_program_pair(cfg, slots, max_len, hw,
                                 autotune.active_generation(),
                                 paged, page_size, page_pool, kv_quant)


@functools.lru_cache(maxsize=32)
def _compile_program_pair(cfg: ArchConfig, slots: int, max_len: int,
                          hw: HardwareModel, generation: str,
                          paged: bool = False, page_size: int = 16,
                          page_pool: int | None = None,
                          kv_quant: str | None = None) -> ProgramPair:
    """Compile the stateful serving pair: a batch-1 prefill Program
    (full causal forward + cache writes at the admitted slot) and a
    decode Program (one token per slot against the cache), sharing one
    persistent region table so a single runtime ``ProgramState``
    addresses both.  Cached per (config, slots, max_len, hw,
    tuned-cache generation); tuned decode entries are looked up at
    ``batch=slots`` (matching ``core/autotune.tune_lm_decode``) and
    prefill entries at ``batch=1``.

    For a windowed config the persistent regions hold
    ``kv_cache_len = min(max_len, attn_window)`` rows per slot; the
    prefill executor converts the full-``max_len`` K/V into the rolling
    (ring) layout at write time and decode overwrites at ``pos %
    cache_len`` — the full-cache and windowed plans differ *only* in
    region shape, never in instruction structure.

    ``paged=True`` selects the third region-plan scheme: the allocator
    mints page pools + a page table (``regions.paged_kv_specs``)
    instead of contiguous rows — ``page_pool`` caps the pool (the HBM
    budget knob, default worst-case) and ``kv_quant="int8"`` stores
    quantized pages with per-page scales.  Paged is mutually exclusive
    with a sliding window (the window is already a shrunk contiguous
    plan; paging it would page a ring, which buys nothing)."""
    if paged and cfg.attn_window:
        raise NotImplementedError(
            f"paged KV and attn_window are mutually exclusive "
            f"({cfg.name} has window={cfg.attn_window}); the window "
            f"plan already bounds resident rows")
    # Family dispatch: decoder-only transformers (dense / MoE) lower
    # right here; the recurrent and encoder-memory families through
    # their own modules' graph builders.  Importing the module is what
    # registers its named-state hook, so ``state_specs`` below resolves
    # for every dispatched family and raises the full blocker list for
    # the rest (vlm).
    fam = cfg.family
    if fam == "ssm":
        from . import rwkv as gmod
    elif fam == "hybrid":
        from . import zamba2 as gmod
    elif fam == "audio":
        from . import whisper as gmod
    else:
        gmod = None
        _require_dense(cfg)
    specs, caps = state_specs(cfg, slots, max_len)
    if paged and not caps.paged:
        raise NotImplementedError(
            f"{cfg.name} is blocked by: family {fam!r} state is not "
            f"pageable (paged plans assume KV-row granularity) — it "
            f"still runs the scan forward")
    pre_tuned, cost_model = _tuned_context(cfg.name, 1, hw, generation)
    dec_tuned, _ = _tuned_context(cfg.name, slots, hw, generation)
    pg = page_size if paged else None
    if gmod is None:
        pre_graph = to_graph(cfg, batch=1, seq=max_len, write_cache=True,
                             page_size=pg,
                             kv_quant=kv_quant if paged else None)
        dec_graph = to_decode_graph(cfg, slots=slots, max_len=max_len,
                                    page_size=pg,
                                    kv_quant=kv_quant if paged else None)
    else:
        pre_graph = gmod.to_graph(cfg, seq=max_len, write_cache=True)
        dec_graph = gmod.to_decode_graph(cfg, slots=slots, max_len=max_len)
    pre_graph.name = cfg.name + ".prefill"
    pre_sched = compile_model(pre_graph, hw, tuned=pre_tuned,
                              cost_model=cost_model)
    dec_sched = compile_model(dec_graph, hw, tuned=dec_tuned,
                              cost_model=cost_model)
    pre_plan = allocate_regions(pre_graph, pre_sched)
    dec_plan = allocate_regions(dec_graph, dec_sched)
    # One persistent table, one base: the minted state region ids
    # coincide across the pair (regions.py invariant), so prefill-
    # written state buffers are read by decode ops under the same ids.
    base = max(len(pre_plan.regions), len(dec_plan.regions))
    paged_plan = None
    if paged:
        specs, paged_plan = paged_kv_specs(
            n_layers=cfg.n_layers, kv_heads=cfg.n_kv_heads, head_dim=cfg.hd,
            slots=slots, max_len=max_len, page_size=page_size,
            n_pages=page_pool,
            kv_dtype=("int8" if kv_quant == "int8"
                      else jnp.dtype(cfg.kv_jdtype).name))
    pre_plan = extend_with_persistent(pre_plan, specs, base)
    dec_plan = extend_with_persistent(dec_plan, specs, base)
    return ProgramPair(
        prefill=lower_to_program(pre_graph, pre_sched, pre_plan),
        decode=lower_to_program(dec_graph, dec_sched, dec_plan),
        slots=slots, max_len=max_len, paged=paged_plan, caps=caps)


def compile_draft_pair(target_cfg: ArchConfig, draft_cfg: ArchConfig,
                       slots: int = 8, max_len: int = 256,
                       hw: HardwareModel = TPU_V5E) -> ProgramPair:
    """Compile the speculative-decode *draft* (prefill, decode) pair —
    ``compile_program_pair`` verbatim on the draft config, same
    (slots, max_len) geometry as the target — after validating the
    draft can propose for ``target_cfg``.

    The contract is token-level: the draft proposes ids the target
    verifies, so the vocabularies must be identical (anything else is a
    silent id-space mismatch, not an accuracy tradeoff).  Sliding
    windows are rejected on either side: accept/rollback truncates the
    per-slot length, which is only safe while every cache row below the
    truncated length is still resident — a ring that wrapped during the
    speculative burst would have overwritten history the rollback
    re-exposes."""
    if draft_cfg.vocab != target_cfg.vocab:
        raise ValueError(
            f"draft/target vocab mismatch ({draft_cfg.vocab} vs "
            f"{target_cfg.vocab}): speculative decode exchanges token "
            f"ids, the vocabularies must be identical")
    if target_cfg.attn_window or draft_cfg.attn_window:
        raise NotImplementedError(
            "speculative decode over windowed attention: rollback "
            "truncates lengths, but a wrapped ring has already "
            "overwritten the rows the truncation re-exposes")
    if target_cfg.family != "dense":
        raise NotImplementedError(
            f"speculative decode requires a speculatable target "
            f"(family state caps): {target_cfg.name} is "
            f"family={target_cfg.family}, whose state rollback is not "
            f"length-truncation")
    _require_dense(draft_cfg)
    return compile_program_pair(draft_cfg, slots=slots, max_len=max_len,
                                hw=hw)


def program_forward(params, tokens, cfg: ArchConfig, *,
                    impl: str = "auto", hw: HardwareModel = TPU_V5E,
                    interpret: bool | None = None):
    """tokens (B, S) -> logits (B, S, V) through the compiled Program.

    The serving fast path: compiles the config once (cached) and
    executes the instruction stream through runtime/executor.py — no
    per-call re-derivation of tilings or fusion.  Unlike ``forward``
    this returns the logits array directly (no aux dict; the dense
    family has none)."""
    from ..runtime.executor import jitted_runner
    program = compile_program(cfg, batch=tokens.shape[0],
                              seq=tokens.shape[1], hw=hw)
    runner = jitted_runner(program, impl=impl, interpret=interpret)
    return runner(params, tokens)


# --- decode -----------------------------------------------------------------------
def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               n_vision: int | None = None) -> dict:
    KV, hd, L = cfg.n_kv_heads, cfg.hd, cfg.n_layers
    dt = cfg.kv_jdtype
    cache_len = kv_cache_len(cfg, max_len)
    cache = {
        "k": jnp.zeros((L, batch, KV, cache_len, hd), dt),
        "v": jnp.zeros((L, batch, KV, cache_len, hd), dt),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.cross_attn_every:
        G = cfg.n_layers // cfg.cross_attn_every
        Tv = n_vision or cfg.n_vision_tokens
        cache["cross_k"] = jnp.zeros((G, batch, KV, Tv, hd), dt)
        cache["cross_v"] = jnp.zeros((G, batch, KV, Tv, hd), dt)
    return cache


def _write_cache(cache_k, cache_v, k_new, v_new, slot):
    """Insert (B, KV, hd) at per-sequence slot of (B, KV, S, hd)."""
    def upd(c, x, s):
        return jax.lax.dynamic_update_slice_in_dim(c, x[:, None], s, axis=1)
    k = jax.vmap(upd)(cache_k, k_new, slot)
    v = jax.vmap(upd)(cache_v, v_new, slot)
    return k, v


def _attention_decode(h1, p, cfg, ck, cv, pos, cos, sin, *, impl):
    """h1 (B, D); ck/cv (B, KV, S, hd); pos (B,).  Rolling window cache."""
    B, D = h1.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    S = ck.shape[2]
    q = (h1 @ p["wq"]).reshape(B, H, hd)
    k = (h1 @ p["wk"]).reshape(B, KV, hd)
    v = (h1 @ p["wv"]).reshape(B, KV, hd)
    if cos is not None:
        q = apply_rope(q, cos[:, None], sin[:, None])
        k = apply_rope(k, cos[:, None], sin[:, None])
    slot = pos % S                                   # rolling (window) cache
    ck, cv = _write_cache(ck, cv, k.astype(ck.dtype), v.astype(cv.dtype),
                          slot)
    out = decode_attention(q, ck, cv, kv_len=ring_kv_len(pos, S), impl=impl)
    return (out.reshape(B, H * hd) @ p["wo"]), ck, cv


def decode_step(params, cache, tokens, cfg: ArchConfig, *,
                impl: str = "auto"):
    """tokens (B,) -> (logits (B, V), new cache).  pos advances by 1."""
    B = tokens.shape[0]
    pos = cache["pos"]
    h = params["embed"][tokens].astype(cfg.jdtype)
    rot = Rotary(cfg.hd, cfg.rope_theta)
    cos, sin = rot.freqs(pos)                        # (B, hd/2)

    def body(carry, xs):
        p_i, ck, cv = xs
        a_in = _norm(carry, p_i, cfg, "attn_norm")
        a, ck, cv = _attention_decode(a_in, p_i, cfg, ck, cv, pos, cos, sin,
                                      impl=impl)
        carry = carry + a
        m, _ = _mlp(_norm(carry, p_i, cfg, "mlp_norm")[:, None], p_i, cfg)
        carry = carry + m[:, 0]
        return carry, (ck, cv)

    if cfg.cross_attn_every:
        per = cfg.cross_attn_every
        G = cfg.n_layers // per
        blocks = jax.tree.map(
            lambda x: x.reshape((G, per) + x.shape[1:]), params["blocks"])
        kc = cache["k"].reshape((G, per) + cache["k"].shape[1:])
        vc = cache["v"].reshape((G, per) + cache["v"].shape[1:])

        def group(carry, xs):
            cross_p, self_p, kc_g, vc_g, xk, xv = xs
            a_in = _norm(carry, cross_p, cfg, "attn_norm")
            H, hd = cfg.n_heads, cfg.hd
            q = (a_in @ cross_p["wq"]).reshape(B, H, hd)
            a = decode_attention(q, xk, xv, impl=impl)
            a = a.reshape(B, H * hd) @ cross_p["wo"]
            carry = carry + jnp.tanh(cross_p["gate"]).astype(carry.dtype) * a
            carry, ys = jax.lax.scan(body, carry, (self_p, kc_g, vc_g))
            return carry, ys

        h, (k_new, v_new) = jax.lax.scan(
            group, h, (params["cross_blocks"], blocks, kc, vc,
                       cache["cross_k"], cache["cross_v"]))
        k_new = k_new.reshape(cache["k"].shape)
        v_new = v_new.reshape(cache["v"].shape)
    elif cfg.n_experts > 0 and cfg.moe_every > 1:
        per = cfg.moe_every
        G = cfg.n_layers // per
        dense = jax.tree.map(
            lambda x: x.reshape((G, per - 1) + x.shape[1:]),
            params["blocks"])
        kc = cache["k"].reshape((G, per) + cache["k"].shape[1:])
        vc = cache["v"].reshape((G, per) + cache["v"].shape[1:])

        def group(carry, xs):
            moe_p, dense_p, kc_g, vc_g = xs
            carry, ys_d = jax.lax.scan(
                body, carry, (dense_p, kc_g[:per - 1], vc_g[:per - 1]))
            carry, ys_m = body(carry, (moe_p, kc_g[per - 1], vc_g[per - 1]))
            return carry, (ys_d, ys_m)

        h, ((kd, vd), (km, vm)) = jax.lax.scan(
            group, h, (params["moe_blocks"], dense, kc, vc))
        k_new = jnp.concatenate([kd, km[:, None]], axis=1).reshape(
            cache["k"].shape)
        v_new = jnp.concatenate([vd, vm[:, None]], axis=1).reshape(
            cache["v"].shape)
    else:
        h, (k_new, v_new) = jax.lax.scan(
            body, h, (params["blocks"], cache["k"], cache["v"]))

    h = _norm(h, params, cfg, "final_norm")
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = h @ head
    new_cache = dict(cache)
    new_cache.update({"k": k_new, "v": v_new, "pos": pos + 1})
    return logits, new_cache
