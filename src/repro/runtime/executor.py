"""Program executor — runs the compiler's instruction stream (§5.2).

The Snowflake accelerator executes exactly what the compiler emitted;
here the executor walks a ``core/program.py::Program`` and dispatches
each op to the Pallas kernels with the schedule's *pre-resolved*
decisions — conv strip tiling, strip storage, loop order, matmul block,
attention (block_q, block_kv), and the fused epilogue flags.  The LM
families dispatch through the same loop as the CNNs: ``embed`` /
``norm`` / ``flash_attention`` / ``mul`` ops joined ``conv2d`` /
``matmul`` / the pools when the transformer lowering landed.

Stateful Programs (the serving pair) add a **ProgramState** carrier:
the persistent KV-cache buffers (keyed by the allocator's persistent
region ids) plus the per-slot sequence lengths.  ``run_prefill``
executes the prefill Program for one admitted request, writing each
block's K/V into the cache regions at the admitted slot;
``run_decode`` advances every slot by one token through the
``decode_attention`` ops.  Both thread the state functionally —
(params, x, state) -> (out, new_state) — and their jitted wrappers
donate the state so XLA updates the cache buffers in place.

Invariants:

* **Nothing is re-derived at run time.**  Every kernel call below
  passes the op's resolved schedule through verbatim (``tiling=``,
  ``block=``, ``block_q=``/``block_kv=``, ``strip_storage=``); the
  executor never calls a chooser.  If a kernel needs a decision the op
  does not carry, that is a lowering bug in core/program.py.
* **Region ids are allocator-owned.**  The region file below is keyed
  by the §5.1 ``RegionPlan`` ids embedded in the ops; the executor
  reads ``op.in_region``/``k_region``/``v_region``/``bypass_region``
  (and for stateful ops ``k_cache_region``/``v_cache_region``) and
  writes ``op.out_region``, and never maps a name to an id itself.
* **``run`` is functionally pure** (params, x -> output) and
  jit-compatible; models wrap it in ``jax.jit`` per (program, impl)
  via ``jitted_runner``.

``x`` is whatever the program's input region expects: an (B, H, W, C)
image batch for CNN programs, an (B, S) int32 token batch for LM
programs (the first op is then the ``embed`` gather).
"""
from __future__ import annotations

import collections
import dataclasses
import json
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import numpy as np

from ..core.program import Program, ProgramOp, ProgramPair
from ..core.regions import (PAGE_TABLE_REGION, PagedPlan, kv_cache_rows,
                            pages_for_len)
from ..kernels.conv2d import avgpool2d_ref, conv2d, maxpool2d_ref
from ..kernels.decode_attention import (decode_attention, fold_kv,
                                        paged_decode_attention, ring_kv_len,
                                        ring_positions, unfold_kv)
from ..kernels.flash_attention import flash_attention
from ..kernels.matmul import matmul

__all__ = ["run", "jitted_runner", "ProgramState", "init_program_state",
           "run_prefill", "run_prefill_chunk", "run_decode",
           "jitted_prefill_runner", "jitted_chunk_runner",
           "jitted_decode_runner", "PagePool", "paged_pool_regions",
           "sync_page_table", "apply_page_copies", "TraceRecord",
           "ExecutorTrace", "trace_program", "op_scope"]


def _param(params, key: str | None):
    """Resolve a ProgramOp param path.

    ``"layer_03"``       -> params["layer_03"]           (CNN groups)
    ``"blocks/wq:3"``    -> params["blocks"]["wq"][3]    (stacked LM blocks)
    ``"blocks:3"``       -> every leaf of params["blocks"] at index 3
                            (whole-block group path — coarse family ops)
    ``"final_norm"``     -> params["final_norm"]
    """
    if key is None:
        return None
    path, _, idx = key.partition(":")
    p = params
    for part in path.split("/"):
        p = p[part]
    if not idx:
        return p
    i = int(idx)
    if isinstance(p, dict):
        return jax.tree.map(lambda a: a[i], p)
    return p[i]


def _attention_heads(op: ProgramOp, regions: dict):
    """Reshape the flat q/k/v regions to per-head layout and apply RoPE
    when the spec says so — the shared front half of every prefill
    flash dispatch (whole and chunked), so the two can never drift."""
    # Lazy import: models.common is the one shared home of the rotary
    # helpers and models/cnn.py imports this module at load time.
    from ..models.common import Rotary, apply_rope
    a = op.attn
    q, k, v = regions[op.in_region], regions[op.k_region], regions[op.v_region]
    B, S = q.shape[0], q.shape[1]
    q = q.reshape(B, S, a.heads, a.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, a.kv_heads, a.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(B, S, a.kv_heads, a.head_dim).transpose(0, 2, 1, 3)
    if a.rope_theta:
        cos, sin = Rotary(a.head_dim, a.rope_theta).freqs(jnp.arange(S))
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return q, k, v


def _run_attention(op: ProgramOp, regions: dict, *, impl: str,
                   interpret: bool | None, return_kv: bool = False):
    """Dispatch one flash_attention op: reshape the flat q/k/v regions
    to per-head layout, apply RoPE when the spec says so, and call the
    kernel with the schedule's exact (block_q, block_kv).

    ``return_kv=True`` additionally hands back the per-head (post-RoPE)
    K and V — exactly what a cache-writing prefill op stores in its
    persistent regions."""
    a = op.attn
    q, k, v = _attention_heads(op, regions)
    B, S = q.shape[0], q.shape[2]
    out = flash_attention(q, k, v, causal=a.causal, window=a.window,
                          block_q=a.block_q, block_kv=a.block_kv,
                          impl=impl, interpret=interpret)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, a.heads * a.head_dim)
    if return_kv:
        return out, k, v
    return out


def _run_norm(op: ProgramOp, src: jax.Array, params) -> jax.Array:
    from ..models.common import layer_norm, rms_norm
    w = _param(params, op.param_key)
    if op.norm_kind == "layernorm":
        return layer_norm(src, w, _param(params, op.param_key_b))
    if op.norm_kind == "nonparametric":
        return layer_norm(src)
    return rms_norm(src, w)


_FAMILY_KERNELS = ("wkv", "ssm_scan", "moe_dispatch", "cross_attention")


def _write_state_row(caches: dict, rid: int, val: jax.Array, slot) -> None:
    """Scatter a prefill op's (1, ...) final state into the
    (slots, ...) persistent region at the admitted slot."""
    buf = caches[rid]
    row = val[0].astype(buf.dtype)
    caches[rid] = jax.lax.dynamic_update_slice(
        buf, row[None], (slot,) + (0,) * row.ndim)


def _run_family_op(op: ProgramOp, src: jax.Array, regions: dict, params,
                   caches: dict | None, *, slot=None, length=None,
                   live=None, impl: str, interpret: bool | None):
    """Dispatch one family op (coarse recurrent block, MoE dispatch, or
    cross-attention over read-only encoder memory).

    Prefill and decode share one arm per kernel, split on the operand
    rank — (B, S, D) is a prefill pass, (slots, D) a decode tick —
    because the instruction stream is the only difference the lowering
    leaves between the two.  State-carrying ops resolve their buffers
    through ``op.state_regions`` (the allocator's generic persistent
    rids, in the family's documented order) and never assume a KV
    shape; prefill scatters the block's final state at the admitted
    slot, decode reads/writes all slots with dead ones masked to their
    old rows via ``live``.  ``caches=None`` (stateless ``run``) skips
    the writes — the recurrent blocks still compute from their zero
    init, matching the legacy scan forward."""
    if op.kernel == "moe_dispatch":
        from ..models.moe import moe_mlp
        c = dict(op.op_cfg)
        p = _param(params, op.param_key)
        shp = src.shape
        vc = None
        if length is not None and src.ndim == 3:
            # Right-padded prefill rows: pad tokens must not claim
            # expert capacity (models/moe sentinel-expert path).
            vc = jnp.asarray(length, jnp.int32)
        out, _ = moe_mlp(src.reshape(-1, shp[-1]), p["router"],
                         p["w_gate"], p.get("w_up", p["w_gate"]),
                         p["w_down"], top_k=c["top_k"],
                         capacity_factor=c["capacity_factor"],
                         activation=c["activation"], gated=c["gated"],
                         valid_count=vc)
        out = out.reshape(shp).astype(src.dtype)
        if op.fuse_bypass and op.bypass_region is not None:
            out = out + regions[op.bypass_region]
        return out
    if op.kernel == "cross_attention":
        if caches is None:
            raise ValueError(
                f"op {op.name} reads persistent encoder memory; use "
                f"run_prefill/run_decode with a ProgramState")
        a = op.attn
        ck, cv = caches[op.k_cache_region], caches[op.v_cache_region]
        if src.ndim == 3:                         # prefill: one slot
            B, S = src.shape[:2]
            q = src.reshape(B, S, a.heads, a.head_dim).transpose(0, 2, 1, 3)
            km = jax.lax.dynamic_slice_in_dim(ck, slot, 1, axis=0)
            vm = jax.lax.dynamic_slice_in_dim(cv, slot, 1, axis=0)
            out = flash_attention(
                q, km.transpose(0, 2, 1, 3).astype(q.dtype),
                vm.transpose(0, 2, 1, 3).astype(q.dtype),
                causal=False, block_q=a.block_q, block_kv=a.block_kv,
                impl=impl, interpret=interpret)
            return (out.transpose(0, 2, 1, 3)
                    .reshape(B, S, a.heads * a.head_dim))
        B = src.shape[0]                          # decode: all slots
        q = src.reshape(B, a.heads, a.head_dim)
        out = decode_attention(
            q, ck.transpose(0, 2, 1, 3).astype(q.dtype),
            cv.transpose(0, 2, 1, 3).astype(q.dtype),
            block_kv=a.block_kv, impl=impl, interpret=interpret)
        return out.reshape(B, a.heads * a.head_dim)
    # coarse recurrent block ops ("wkv" | "ssm_scan")
    p = _param(params, op.param_key)
    if src.ndim == 3:                             # prefill pass
        if op.kernel == "wkv":
            from ..models.rwkv import block_prefill
        else:
            from ..models.zamba2 import block_prefill
        out, states = block_prefill(src, p, impl=impl, length=length)
        if caches is not None and op.state_regions:
            for rid, val in zip(op.state_regions, states):
                _write_state_row(caches, rid, val, slot)
        return out
    if caches is None:
        raise ValueError(
            f"op {op.name} needs a ProgramState (persistent state "
            f"regions); use run_decode for decode Programs")
    states = [caches[r] for r in op.state_regions]
    if op.kernel == "wkv":
        from ..models.rwkv import block_decode
        out, new = block_decode(src, p, *states)
    else:
        from ..models.zamba2 import block_decode
        out, new = block_decode(src, p, states[0], states[1], impl=impl)
    for rid, old, fresh in zip(op.state_regions, states, new):
        fresh = fresh.astype(old.dtype)
        if live is not None:
            keep = live.reshape((-1,) + (1,) * (old.ndim - 1))
            fresh = jnp.where(keep, fresh, old)
        caches[rid] = fresh
    return out


def _run_op(op: ProgramOp, src: jax.Array, regions: dict, params, *,
            impl: str, interpret: bool | None, pos=None) -> jax.Array:
    """Dispatch one (stateless) op with its pre-resolved schedule."""
    if op.kernel == "conv2d":
        p = _param(params, op.param_key)
        bypass = (regions[op.bypass_region]
                  if op.fuse_bypass and op.bypass_region is not None
                  else None)
        return conv2d(
            src, p["w"], stride=op.stride, pad=op.pad,
            bias=p["b"] if op.fuse_bias else None,
            activation=op.fuse_activation, bypass=bypass,
            bypass_first=op.bypass_first, fuse_pool=op.fuse_pool,
            strip_storage=op.strip_storage or "auto",
            tiling=op.conv_tiling, dataflow=op.dataflow,
            impl=impl, interpret=interpret)
    if op.kernel == "matmul":
        p = _param(params, op.param_key)
        w = p["w"] if isinstance(p, dict) else p
        if op.transpose_w:
            w = w.T
        if op.flatten_input:
            src = src.reshape(src.shape[0], -1)
        bypass = (regions[op.bypass_region]
                  if op.fuse_bypass and op.bypass_region is not None
                  else None)
        if bypass is not None and op.flatten_input:
            bypass = bypass.reshape(bypass.shape[0], -1)
        return matmul(
            src, w,
            bias=(p["b"] if isinstance(p, dict) and op.fuse_bias
                  else None),
            activation=op.fuse_activation, bypass=bypass,
            dataflow=op.dataflow, block=op.block,
            impl=impl, interpret=interpret)
    if op.kernel == "flash_attention":
        return _run_attention(op, regions, impl=impl, interpret=interpret)
    if op.kernel == "embed":
        table = _param(params, op.param_key)
        out = table[src]
        if op.param_key_b is not None:
            pe = _param(params, op.param_key_b)
            if src.ndim >= 2:      # prefill/stateless: rows [0, S)
                out = out + pe[: src.shape[1]][None].astype(out.dtype)
            else:                  # decode: each slot's absolute position
                out = out + pe[pos].astype(out.dtype)
        return out
    if op.kernel == "norm":
        return _run_norm(op, src, params)
    if op.kernel == "mul":
        return src * regions[op.in2_region]
    if op.kernel == "add":
        return src + regions[op.in2_region]
    if op.kernel == "maxpool":
        return maxpool2d_ref(src, window=op.window, stride=op.stride,
                             pad=op.pad)
    if op.kernel == "avgpool":
        return avgpool2d_ref(src, window=op.window, stride=op.stride,
                             pad=op.pad)
    raise NotImplementedError(f"unknown program kernel {op.kernel}")


def op_scope(op: ProgramOp) -> str:
    """The ``jax.named_scope`` an op's dispatch runs under,
    ``<kernel>.<name>`` (``decode_attention.l3.attn``): everything the
    op emits, its cache writes and layout copies included, carries it
    in the HLO ``op_name`` metadata, kernel first so that a trace
    reader matches an op kind by prefix."""
    return f"{op.kernel}.{op.name}"


def run(program: Program, params, x: jax.Array, *, impl: str = "auto",
        interpret: bool | None = None) -> jax.Array:
    """Execute ``program`` against ``params`` on input ``x``.

    x: (B, H, W, C) for CNN programs, (B, S) int32 tokens for LM
    programs.  Returns the final op's output (the array living in
    ``program.output_region``).  Cache-writing prefill ops run as plain
    flash attention here (stateless execution ignores the persistent
    regions); ``decode_attention`` ops need state and are rejected —
    use ``run_decode``.
    """
    regions: dict[int, jax.Array] = {program.input_region: x}
    for op in program.ops:
        with jax.named_scope(op_scope(op)):
            if op.kernel == "decode_attention":
                raise ValueError(
                    f"op {op.name} needs a ProgramState (persistent KV "
                    f"regions); use run_decode for decode Programs")
            if op.kernel in _FAMILY_KERNELS:
                regions[op.out_region] = _run_family_op(
                    op, regions[op.in_region], regions, params, None,
                    impl=impl, interpret=interpret)
                continue
            regions[op.out_region] = _run_op(
                op, regions[op.in_region], regions, params, impl=impl,
                interpret=interpret)
    return regions[program.output_region]


# --- stateful Programs (serving prefill/decode pair) -------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class ProgramState:
    """Runtime carrier for a Program pair's persistent regions.

    ``caches`` maps the allocator's persistent region ids to their
    buffers — for the LM pair, one KV region per block and cache side
    in the layout ``regions.kv_cache_spec`` gives it (lane-dense
    (slots, kv_heads, cache_len / f, f * head_dim), or (slots,
    cache_len, kv_heads, head_dim) where head_dim does not fold), where
    cache_len is max_len or the attention window (whichever the §5.1
    plan sized the region at);
    ``lengths`` is the per-slot sequence length (the decode ops'
    position operand, counting absolute tokens even once the ring has
    wrapped).  Registered as a pytree
    so the jitted prefill/decode runners can donate it and XLA aliases
    the cache updates in place.
    """

    caches: dict[int, jax.Array]
    lengths: jax.Array               # (slots,) int32

    def tree_flatten(self):
        rids = tuple(sorted(self.caches))
        return (tuple(self.caches[r] for r in rids) + (self.lengths,), rids)

    @classmethod
    def tree_unflatten(cls, rids, leaves):
        *bufs, lengths = leaves
        return cls(dict(zip(rids, bufs)), lengths)


def init_program_state(pair: ProgramPair | Program) -> ProgramState:
    """Allocate zeroed persistent buffers from the plan's persistent
    regions (their shape/dtype is allocator-recorded identity)."""
    plan = (pair.decode.plan if isinstance(pair, ProgramPair) else pair.plan)
    name = (pair.decode.name if isinstance(pair, ProgramPair) else pair.name)
    persistent = plan.persistent_regions()
    if not persistent:
        raise ValueError(
            f"program {name} reserves no persistent regions "
            f"({len(plan.regions)} transient only) — stateful execution "
            f"needs a plan extended via regions.extend_with_persistent "
            f"(e.g. transformer.compile_program_pair)")
    caches = {r.rid: jnp.zeros(r.shape, jnp.dtype(r.dtype))
              for r in persistent}
    # Paged plans key slot count off the page table (pools are
    # slot-agnostic); contiguous plans off any cache region's axis 0.
    pt = next((r for r in persistent if r.name == PAGE_TABLE_REGION), None)
    slots = (pt if pt is not None else persistent[0]).shape[0]
    return ProgramState(caches, jnp.zeros((slots,), jnp.int32))


def _to_region(rows: jax.Array, fold: int | None) -> jax.Array:
    """Head-major (..., KV, S, hd) rows in a KV region's layout: folded
    lane-dense (a free reshape), or row-major (..., S, KV, hd)."""
    return jnp.swapaxes(rows, -3, -2) if fold is None else fold_kv(rows, fold)


def _from_region(buf: jax.Array, fold: int | None) -> jax.Array:
    """Inverse of ``_to_region``: a KV region's rows as (..., KV, S, hd)."""
    return jnp.swapaxes(buf, -3, -2) if fold is None else unfold_kv(buf, fold)


def _write_prefill_cache(caches: dict, op: ProgramOp, k, v, slot,
                         length) -> None:
    """Store a prefill op's per-head K/V — (1, KVh, S, hd) — into the
    cache regions at the admitted slot, in the regions' layout
    (``op.attn.kv_fold``).  The rows start at row 0 and are whole, so
    into a lane-dense region the write is a reshape of the K/V as the
    prefill computed them.

    A window-sized region (cache_len < S, the §5.1 rolling-window plan)
    receives the **ring layout** the decode ops expect, via the shared
    ``ring_positions`` rule: ring slot j holds the latest prompt
    position ``p < length`` with ``p % cache_len == j`` — the same
    keep-last-W conversion ``to_graph``'s cache export performs,
    generalized to a runtime ``length``.  Every ring slot is written
    (slots with no valid position duplicate an early row, overwritten
    by decode before ``ring_kv_len`` ever admits them), so re-admission
    into a previously used slot can never leak a dead request's stale
    rows."""
    fold = op.attn.kv_fold
    for rid, val in ((op.k_cache_region, k), (op.v_cache_region, v)):
        buf = caches[rid]
        rows = val[0].astype(buf.dtype)                       # (KV, S, hd)
        S, cache_len = rows.shape[1], kv_cache_rows(buf.shape, fold)
        if cache_len < S:
            rows = rows[:, ring_positions(length, cache_len, S)]
        caches[rid] = jax.lax.dynamic_update_slice(
            buf, _to_region(rows, fold)[None], (slot, 0, 0, 0))


def _write_prefill_cache_paged(caches: dict, op: ProgramOp, k, v, slot,
                               length, write_from) -> None:
    """Paged flavor of the prefill cache write: scatter the prompt's
    K/V into the slot's table-mapped pool pages, one whole page per
    scatter row.

    ``write_from`` is the shared-prefix redirect (a page multiple): the
    pages covering rows ``< write_from`` are COW-mapped from a donor
    slot and must not be touched, so their scatter destination is the
    null page 0 — the write stays dense and branch-free.  Unallocated
    tail entries are already 0 in the table and land there too.  Rows
    at ``>= length`` (prompt right-padding) are zeroed before the write
    so an int8 tail page's scale is set by real rows only."""
    a = op.attn
    pg = a.page_size
    pt = caches[op.page_table_region]
    pt_row = jax.lax.dynamic_slice_in_dim(pt, slot, 1, axis=0)[0]
    quant = op.k_scale_region is not None
    scales = ((op.k_scale_region, op.v_scale_region) if quant
              else (None, None))
    for rid, srid, val in ((op.k_cache_region, scales[0], k),
                          (op.v_cache_region, scales[1], v)):
        buf = caches[rid]
        row = val[0].transpose(1, 0, 2)                       # (S, KV, hd)
        S = row.shape[0]
        row = jnp.where(jnp.arange(S)[:, None, None] < length, row, 0)
        pages = row.reshape(S // pg, pg, row.shape[1], row.shape[2])
        dest = jnp.where(jnp.arange(S // pg) * pg
                         >= jnp.asarray(write_from, jnp.int32),
                         pt_row, 0)
        if quant:
            from ..core.quant import int8_quantize_pages
            q, sc = int8_quantize_pages(pages)
            caches[rid] = buf.at[dest].set(q)
            caches[srid] = caches[srid].at[dest].set(sc)
        else:
            caches[rid] = buf.at[dest].set(pages.astype(buf.dtype))


def run_prefill(program: Program, params, tokens: jax.Array,
                state: ProgramState, slot, length, write_from=0, *,
                impl: str = "auto", interpret: bool | None = None):
    """Execute the prefill Program for one admitted request.

    tokens: (1, max_len) int32, the prompt right-padded (rows past
    ``length`` are masked downstream by the per-slot length, so their
    K/V content is inert).  Writes each block's K/V into the persistent
    cache regions at ``slot`` — window-sized regions get the rolling
    (ring) layout, see ``_write_prefill_cache`` — sets
    ``lengths[slot] = length`` and returns
    (logits (1, max_len, vocab), new_state).
    """
    regions: dict[int, jax.Array] = {program.input_region: tokens}
    caches = dict(state.caches)
    for op in program.ops:
        with jax.named_scope(op_scope(op)):
            src = regions[op.in_region]
            if (op.kernel == "flash_attention"
                    and op.k_cache_region is not None):
                out, k, v = _run_attention(op, regions, impl=impl,
                                           interpret=interpret,
                                           return_kv=True)
                if op.page_table_region is not None:
                    _write_prefill_cache_paged(caches, op, k, v, slot,
                                               length, write_from)
                else:
                    _write_prefill_cache(caches, op, k, v, slot, length)
                regions[op.out_region] = out
                continue
            if op.kernel in _FAMILY_KERNELS:
                regions[op.out_region] = _run_family_op(
                    op, src, regions, params, caches, slot=slot,
                    length=length, impl=impl, interpret=interpret)
                continue
            regions[op.out_region] = _run_op(op, src, regions, params,
                                             impl=impl, interpret=interpret)
    lengths = state.lengths.at[slot].set(jnp.asarray(length, jnp.int32))
    return regions[program.output_region], ProgramState(caches, lengths)


# --- chunked prefill (throughput-grade serving) ------------------------------------
def _run_attention_chunk(op: ProgramOp, regions: dict, caches: dict,
                         slot, start, *, impl: str,
                         interpret: bool | None):
    """One flash op of a *chunk* prefill pass: identical q/k/v + RoPE
    front half as the whole-prefill dispatch, but the K/V columns at
    positions ``< start`` are substituted from the slot's persistent
    cache rows before the kernel call.

    The chunk pass always runs over the full (B, max_len) padded token
    buffer — embed/norm/matmul are position-local, so the fresh rows at
    ``>= start`` are bitwise what a whole prefill computes there, and
    the substituted history rows were themselves written by earlier
    chunks (induction).  Feeding the *same* (block_q, block_kv) flash
    kernel the same shapes keeps the reduction order identical, so a
    chunked prefill reproduces the whole-prefill outputs bit for bit at
    its chunk rows.

    History substitution per region plan:

    * contiguous — ``cache[slot]`` is already position-indexed;
    * rolling ring — position ``p`` lives at ring row ``p %
      cache_len``, valid only for the window ``start - cache_len <= p <
      start`` (older positions are window-masked inside the kernel, so
      their column content is inert);
    * paged — gather through the slot's page-table row (rows whose page
      is still null can only be positions ``>= start``, never
      selected).
    """
    a = op.attn
    q, k, v = _attention_heads(op, regions)
    B, S = q.shape[0], q.shape[2]
    pos = jnp.arange(S)
    if op.page_table_region is not None:
        pg = a.page_size
        pt_rows = caches[op.page_table_region][slot]   # (B, pages_per_slot)
        page = jnp.take_along_axis(pt_rows, pos[None] // pg, axis=1)
        hk = caches[op.k_cache_region][page, pos[None] % pg]
        hv = caches[op.v_cache_region][page, pos[None] % pg]
        hk, hv = hk.transpose(0, 2, 1, 3), hv.transpose(0, 2, 1, 3)
        valid = pos[None] < start[:, None]
    else:
        buf_k, buf_v = caches[op.k_cache_region], caches[op.v_cache_region]
        cache_len = kv_cache_rows(buf_k.shape, a.kv_fold)
        ring = pos % cache_len
        hk = _from_region(buf_k[slot], a.kv_fold)[:, :, ring]  # (B,KV,S,hd)
        hv = _from_region(buf_v[slot], a.kv_fold)[:, :, ring]
        valid = ((pos[None] < start[:, None])
                 & (pos[None] >= start[:, None] - cache_len))
    m = valid[:, None, :, None]                        # (B, 1, S, 1)
    k = jnp.where(m, hk.astype(k.dtype), k)
    v = jnp.where(m, hv.astype(v.dtype), v)
    out = flash_attention(q, k, v, causal=a.causal, window=a.window,
                          block_q=a.block_q, block_kv=a.block_kv,
                          impl=impl, interpret=interpret)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, a.heads * a.head_dim)
    return out, k, v


def _write_chunk_cache(caches: dict, op: ProgramOp, k, v, slot, start,
                       stop, length) -> None:
    """Store a chunk's fresh K/V rows — (B, KVh, S, hd), rows ``[start,
    stop)`` per batch entry — into the cache regions.  The masks select
    real rows on the regions' rows unfolded (``_from_region``), so in a
    lane-dense region each row is one lane group of a packed row.

    Contiguous regions take the chunk rows in place; the final chunk
    (``stop == length``) extends the write through the padded tail so
    the slot's region ends bitwise-equal to a whole prefill's
    unconditional full-row write.  Window-sized regions (cache_len < S)
    take the ring layout: ring row ``j`` receives the latest chunk
    position ``p < min(stop, length)`` with ``p % cache_len == j``; the
    first chunk seeds every ring row with fresh row 0 — the same
    duplicate-early-row rule ``ring_positions`` applies for ring rows
    no prompt position covers — so re-admission hygiene and whole-
    prefill bit-parity both hold."""
    fold = op.attn.kv_fold
    for rid, val in ((op.k_cache_region, k), (op.v_cache_region, v)):
        buf = caches[rid]
        row = val.astype(buf.dtype)                         # (B, KV, S, hd)
        S, cache_len = row.shape[2], kv_cache_rows(buf.shape, fold)
        old = _from_region(buf[slot], fold)                 # (B, KV, cl, hd)
        if cache_len == S:
            wstop = jnp.where(stop >= length, S, stop)
            pos = jnp.arange(S)
            m = (pos[None] >= start[:, None]) & (pos[None] < wstop[:, None])
            new = jnp.where(m[:, None, :, None], row, old)
        else:
            wstop = jnp.minimum(stop, length)
            j = jnp.arange(cache_len)
            last = (wstop - 1)[:, None]
            p = j[None] + ((last - j[None]) // cache_len) * cache_len
            written = (p >= start[:, None]) & (p < wstop[:, None])
            gathered = jax.vmap(lambda r, idx: r[:, idx])(
                row, jnp.clip(p, 0, S - 1))
            seed = jnp.broadcast_to(row[:, :, :1], old.shape)
            base = jnp.where((start == 0)[:, None, None, None], seed, old)
            new = jnp.where(written[:, None, :, None], gathered, base)
        caches[rid] = buf.at[slot].set(_to_region(new, fold))


def _write_chunk_cache_paged(caches: dict, op: ProgramOp, k, v, slot,
                             start, stop, length, write_from) -> None:
    """Paged flavor of the chunk cache write: scatter the chunk rows
    through the slot's page-table row, one row per scatter entry.

    Rows outside ``[max(start, write_from), stop)`` — and every row on
    the final chunk past ``length`` (prompt right-padding, zeroed as in
    the whole-prefill write) — redirect to the null page 0, so the
    scatter stays dense and COW-shared prefix pages are never touched.
    int8 pools are rejected upstream (``ProgramPair.chunk_blocker``):
    their page scale is set by whole-page quantization, which a
    row-granular chunk write would silently re-base."""
    a = op.attn
    pg = a.page_size
    pt_rows = caches[op.page_table_region][slot]       # (B, pages_per_slot)
    if op.k_scale_region is not None:
        raise NotImplementedError(
            "chunked prefill over int8 paged KV: page scales are "
            "whole-page decisions (see ProgramPair.chunk_blocker)")
    for rid, val in ((op.k_cache_region, k), (op.v_cache_region, v)):
        buf = caches[rid]                              # (n_pages, pg, KV, hd)
        row = val.transpose(0, 2, 1, 3)                # (B, S, KV, hd)
        S = row.shape[1]
        pos = jnp.arange(S)
        wstop = jnp.where(stop >= length, S, stop)
        write = ((pos[None] >= jnp.maximum(start, write_from)[:, None])
                 & (pos[None] < wstop[:, None]))
        rowv = jnp.where(pos[None, :, None, None]
                         < length[:, None, None, None], row, 0)
        page = jnp.where(
            write, jnp.take_along_axis(pt_rows, pos[None] // pg, axis=1), 0)
        caches[rid] = buf.at[page, pos[None] % pg].set(rowv.astype(buf.dtype))


def run_prefill_chunk(program: Program, params, tokens: jax.Array,
                      state: ProgramState, slot, start, stop, length,
                      write_from=None, *, impl: str = "auto",
                      interpret: bool | None = None):
    """Execute the prefill Program for one *chunk* of each of B
    in-flight admissions — rows ``[start[i], stop[i])`` of slot
    ``slot[i]`` — against the full (B, max_len) padded token buffers.

    All operands past ``tokens`` are (B,) int32 vectors: ``length`` is
    each prompt's total row count (``stop == length`` marks the final
    chunk) and ``write_from`` the paged shared-prefix redirect.  Each
    flash op substitutes the slot's already-written cache rows for the
    K/V columns below ``start`` (see ``_run_attention_chunk``), then
    writes the chunk rows back; ``lengths[slot]`` advances to ``stop``
    so the next chunk (or the first decode tick after the final chunk)
    continues exactly where this one stopped.  Returns (logits (B,
    max_len, vocab), new_state) — only rows ``[start, stop)`` of the
    logits are chunk-fresh; the final chunk's ``length - 1`` row is the
    one the engine samples the first token from.

    A full-prompt "chunk" (start 0, stop == length) degenerates to
    ``run_prefill`` semantics, bit for bit."""
    regions: dict[int, jax.Array] = {program.input_region: tokens}
    caches = dict(state.caches)
    slot = jnp.asarray(slot, jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    stop = jnp.asarray(stop, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    write_from = (jnp.zeros_like(start) if write_from is None
                  else jnp.asarray(write_from, jnp.int32))
    for op in program.ops:
        with jax.named_scope(op_scope(op)):
            src = regions[op.in_region]
            if (op.kernel == "flash_attention"
                    and op.k_cache_region is not None):
                out, k, v = _run_attention_chunk(op, regions, caches, slot,
                                                 start, impl=impl,
                                                 interpret=interpret)
                if op.page_table_region is not None:
                    _write_chunk_cache_paged(caches, op, k, v, slot, start,
                                             stop, length, write_from)
                else:
                    _write_chunk_cache(caches, op, k, v, slot, start, stop,
                                       length)
                regions[op.out_region] = out
                continue
            regions[op.out_region] = _run_op(op, src, regions, params,
                                             impl=impl, interpret=interpret)
    lengths = state.lengths.at[slot].set(stop)
    return regions[program.output_region], ProgramState(caches, lengths)


def jitted_chunk_runner(program: Program, impl: str = "auto",
                        interpret: bool | None = None):
    """Compiled chunk prefill: (params, tokens, state, slot, start,
    stop, length, write_from) -> (logits, state), state donated.  One
    executable per in-flight batch width B (XLA re-specializes on the
    leading shape; the engine's chunk batches are small and repeat)."""
    def make():
        def program_chunk(params, tokens, state, slot, start, stop,
                          length, write_from, _program=program):
            return run_prefill_chunk(_program, params, tokens, state,
                                     slot, start, stop, length,
                                     write_from, impl=impl,
                                     interpret=interpret)
        return jax.jit(program_chunk, donate_argnums=(2,))
    return _cached_runner((id(program), impl, interpret, "chunk"), make)


def _write_decode_rows(buf, rows, live, at, window) -> jax.Array:
    """Scatter each slot's new (KV, hd) row into its slot of a KV
    region, at offsets ``at`` (three (slots,) index vectors) in a
    ``window``-shaped block of the slot.  A dead slot's index points
    one past the last slot, so its write is dropped: its rows stay as
    they were without the old row being read, and the scatter updates
    the region in place.  (Reading the old rows back, a per-slot
    gather, makes XLA copy the whole region into another layout.)"""
    slots = buf.shape[0]
    idx = jnp.stack([jnp.where(live, jnp.arange(slots), slots), *at], axis=1)
    dims = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2, 3), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1, 2, 3))
    return jax.lax.scatter(
        buf, idx, rows.reshape((slots,) + window).astype(buf.dtype), dims,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _run_decode_attention(op: ProgramOp, src: jax.Array, k_src: jax.Array,
                          v_src: jax.Array, ck: jax.Array, cv: jax.Array,
                          pos: jax.Array, live: jax.Array, *, impl: str,
                          interpret: bool | None):
    """One decode_attention step against explicit cache buffers: RoPE
    the new q/k at each slot's absolute position, write the new K/V row
    at ``position % cache_len`` (masked per-slot by ``live``), attend
    over the ring-valid rows.  Returns (out (B, heads*head_dim),
    new_k_cache, new_v_cache).  Shared verbatim by ``run_decode`` and
    the replay harness, so a replayed op cannot drift from the
    executor.

    A lane-dense region (``op.attn.kv_fold``) is handed to the kernel as
    it rests, and row ``r`` is written in place at packed row ``r // f``,
    lanes ``[(r % f) * hd, ...)``.  A row-major region is transposed to
    the kernel's head-major operand first: a whole-cache copy."""
    from ..models.common import Rotary, apply_rope
    a = op.attn
    B = src.shape[0]
    q = src.reshape(B, a.heads, a.head_dim)
    k_new = k_src.reshape(B, a.kv_heads, a.head_dim)
    v_new = v_src.reshape(B, a.kv_heads, a.head_dim)
    if a.rope_theta:
        cos, sin = Rotary(a.head_dim, a.rope_theta).freqs(pos)
        q = apply_rope(q, cos[:, None], sin[:, None])
        k_new = apply_rope(k_new, cos[:, None], sin[:, None])
    f, KV, hd = a.kv_fold, a.kv_heads, a.head_dim
    cache_len = kv_cache_rows(ck.shape, f)
    row = pos % cache_len                 # rolling overwrite
    zero = jnp.zeros_like(row)
    if f is None:                         # a slot's (cache_len, KV, hd)
        at, window = (row, zero, zero), (1, KV, hd)
    else:                                 # a slot's (KV, cache_len/f, f*hd)
        at, window = (zero, row // f, (row % f) * hd), (KV, 1, hd)
    ck = _write_decode_rows(ck, k_new, live, at, window)
    cv = _write_decode_rows(cv, v_new, live, at, window)
    k_in, v_in = ((ck, cv) if f is not None
                  else (ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3)))
    out = decode_attention(
        q, k_in, v_in, kv_len=ring_kv_len(pos, cache_len),
        block_kv=a.block_kv, fold=f or 1, impl=impl, interpret=interpret)
    return out.reshape(B, a.heads * a.head_dim), ck, cv


def _run_decode_attention_paged(op: ProgramOp, src: jax.Array,
                                k_src: jax.Array, v_src: jax.Array,
                                ck, cv, ks, vs, pt, pos, live, *,
                                impl: str, interpret: bool | None):
    """Paged flavor of the decode step: the new K/V row scatters into
    the pool page named by the slot's table entry for virtual row ``pos
    % cache_len`` — the same rolling-ring rule as the contiguous path,
    applied through the table — and attention gathers every valid page
    via ``paged_decode_attention``.

    The engine's host-side ``PagePool`` guarantees the write page is
    allocated and private (COW-forked if shared) *before* this runs, so
    the scatter never needs a branch; dead slots redirect to the null
    page 0, which keeps the write dense (their garbage row lands where
    nothing valid ever reads — see ``regions.PagedPlan``).

    int8 pools rewrite the whole target page: the page scale grows to
    admit the new row when needed (``max(old, |row|/127)``) and the
    page is requantized under it — exact when the scale is unchanged,
    the common case.  Returns (out, ck, cv, ks, vs)."""
    from ..models.common import Rotary, apply_rope
    a = op.attn
    B = src.shape[0]
    pg = a.page_size
    pages_per_slot = pt.shape[1]
    cache_len = pages_per_slot * pg
    q = src.reshape(B, a.heads, a.head_dim)
    k_new = k_src.reshape(B, a.kv_heads, a.head_dim)
    v_new = v_src.reshape(B, a.kv_heads, a.head_dim)
    if a.rope_theta:
        cos, sin = Rotary(a.head_dim, a.rope_theta).freqs(pos)
        q = apply_rope(q, cos[:, None], sin[:, None])
        k_new = apply_rope(k_new, cos[:, None], sin[:, None])
    row = pos % cache_len                           # rolling overwrite
    offs = row % pg
    page = jnp.take_along_axis(pt, (row // pg)[:, None], axis=1)[:, 0]
    page = jnp.where(live, page, 0)                 # dead slots -> null page

    if ks is None:
        ck = ck.at[page, offs].set(k_new.astype(ck.dtype))
        cv = cv.at[page, offs].set(v_new.astype(cv.dtype))
    else:
        from ..core.quant import int8_requantize_page

        def write_row(pool, scales, new_row):
            old_page = pool[page]                   # (B, pg, KV, hd)
            old_scale = scales[page]
            amax = jnp.max(jnp.abs(new_row.astype(jnp.float32)),
                           axis=(1, 2))
            new_scale = jnp.maximum(old_scale, amax / 127.0)
            new_scale = jnp.where(new_scale > 0, new_scale, 1.0)
            qp = int8_requantize_page(old_page, old_scale[:, None, None,
                                                          None],
                                      new_scale[:, None, None, None])
            qrow = jnp.clip(jnp.round(new_row.astype(jnp.float32)
                                      / new_scale[:, None, None]),
                            -127, 127).astype(jnp.int8)
            qp = jax.vmap(lambda p, r, o:
                          jax.lax.dynamic_update_slice_in_dim(
                              p, r[None], o, axis=0))(qp, qrow, offs)
            return pool.at[page].set(qp), scales.at[page].set(new_scale)

        ck, ks = write_row(ck, ks, k_new)
        cv, vs = write_row(cv, vs, v_new)

    out = paged_decode_attention(
        q, ck, cv, pt, kv_len=ring_kv_len(pos, cache_len),
        k_scale=ks, v_scale=vs, impl=impl, interpret=interpret)
    return out.reshape(B, a.heads * a.head_dim), ck, cv, ks, vs


def run_decode(program: Program, params, tokens: jax.Array,
               state: ProgramState, mask: jax.Array | None = None, *,
               impl: str = "auto", interpret: bool | None = None):
    """Advance the occupied slots by one token through the decode
    Program.

    tokens: (slots,) int32; mask: (slots,) bool occupancy (None = all
    occupied).  Each ``decode_attention`` op RoPEs the new q/k at the
    slot's absolute position, writes the new K/V row into the
    persistent cache regions at ``position % cache_len`` (the rolling
    ring rule — cache_len is the region's allocator-recorded row count,
    ``min(max_len, attn_window)`` for a windowed plan), and attends
    over ``ring_kv_len(position, cache_len)`` valid rows with the
    schedule's block_kv.  Returns (logits (slots, vocab), new_state)
    with every *occupied* slot's length advanced by one.

    Unoccupied slots are fully inert: their length does not advance and
    their cache rows are not written — a dead slot can never smear
    garbage rows into a region a later request's attention window will
    read (slot-cache hygiene; full-length prefills used to mask this by
    rewriting the whole row region, rolling-window prefills do not).
    Their logits are still garbage the (absent) request never reads.
    """
    regions: dict[int, jax.Array] = {program.input_region: tokens}
    caches = dict(state.caches)
    pos = state.lengths
    live = (jnp.ones(pos.shape, bool) if mask is None
            else jnp.asarray(mask, bool))
    for op in program.ops:
        with jax.named_scope(op_scope(op)):
            src = regions[op.in_region]
            if op.kernel == "decode_attention":
                if op.page_table_region is not None:
                    quant = op.k_scale_region is not None
                    out, ck, cv, ks, vs = _run_decode_attention_paged(
                        op, src, regions[op.k_region], regions[op.v_region],
                        caches[op.k_cache_region], caches[op.v_cache_region],
                        caches[op.k_scale_region] if quant else None,
                        caches[op.v_scale_region] if quant else None,
                        caches[op.page_table_region], pos, live,
                        impl=impl, interpret=interpret)
                    if quant:
                        caches[op.k_scale_region] = ks
                        caches[op.v_scale_region] = vs
                else:
                    out, ck, cv = _run_decode_attention(
                        op, src, regions[op.k_region], regions[op.v_region],
                        caches[op.k_cache_region], caches[op.v_cache_region],
                        pos, live, impl=impl, interpret=interpret)
                caches[op.k_cache_region] = ck
                caches[op.v_cache_region] = cv
                regions[op.out_region] = out
                continue
            if op.kernel in _FAMILY_KERNELS:
                regions[op.out_region] = _run_family_op(
                    op, src, regions, params, caches, live=live,
                    impl=impl, interpret=interpret)
                continue
            regions[op.out_region] = _run_op(op, src, regions, params,
                                             impl=impl, interpret=interpret,
                                             pos=pos)
    return (regions[program.output_region],
            ProgramState(caches, jnp.where(live, pos + 1, pos)))


_RUNNERS: "collections.OrderedDict" = collections.OrderedDict()
_RUNNERS_CAP = 64


def jitted_runner(program: Program, impl: str = "auto",
                  interpret: bool | None = None):
    """One compiled (jit) executor per Program — the models' fast path.

    Keyed by program identity (a Program holds dicts, so it is not
    hashable); the cached closure keeps the program alive, so the id
    cannot be recycled while the entry exists.  LRU-bounded so a
    long-running server cycling through many (config, hw, batch)
    variants cannot pin programs + compiled executables forever.
    """
    def make():
        def program_run(params, x, _program=program):
            return run(_program, params, x, impl=impl, interpret=interpret)
        return jax.jit(program_run)
    return _cached_runner((id(program), impl, interpret, "run"), make)


def _cached_runner(key, make):
    fn = _RUNNERS.get(key)
    if fn is None:
        fn = _RUNNERS[key] = make()
        while len(_RUNNERS) > _RUNNERS_CAP:
            _RUNNERS.popitem(last=False)
    else:
        _RUNNERS.move_to_end(key)
    return fn


def jitted_prefill_runner(program: Program, impl: str = "auto",
                          interpret: bool | None = None):
    """Compiled prefill: (params, tokens, state, slot, length[,
    write_from]) -> (logits, state).  The state argument is donated so
    the cache buffers update in place; ``write_from`` (paged plans
    only) is the shared-prefix row the cache writes start at."""
    def make():
        def program_prefill(params, tokens, state, slot, length,
                            write_from=0, _program=program):
            return run_prefill(_program, params, tokens, state, slot,
                               length, write_from, impl=impl,
                               interpret=interpret)
        return jax.jit(program_prefill, donate_argnums=(2,))
    return _cached_runner((id(program), impl, interpret, "prefill"), make)


def jitted_decode_runner(program: Program, impl: str = "auto",
                         interpret: bool | None = None):
    """Compiled decode tick: (params, tokens, state[, mask]) ->
    (logits, state) with the state donated — the bandwidth-bound
    serving hot loop.  ``mask`` is the (slots,) bool occupancy; omitted
    means every slot is live."""
    def make():
        def program_decode(params, tokens, state, mask=None,
                           _program=program):
            return run_decode(_program, params, tokens, state, mask,
                              impl=impl, interpret=interpret)
        return jax.jit(program_decode, donate_argnums=(2,))
    return _cached_runner((id(program), impl, interpret, "decode"), make)


# --- paged KV runtime (host-side page allocator, §5.1 paged plan) ------------------
class PagePool:
    """Host-side allocator for a pair's §5.1 paged-KV plan.

    The compiler minted the *capacity* (``regions.paged_kv_specs``:
    pool shape, table shape, null page 0); this object owns the
    *assignment* — a free list, per-page refcounts, and a host mirror
    of the device page table.  All decisions (admission, on-demand
    decode pages, COW forks, retirement) happen here between jitted
    calls; the device only ever sees the decided table
    (``sync_page_table``) and whole-page copies (``apply_page_copies``),
    so the jitted prefill/decode runners stay branch-free.

    Refcounts are table-granular, shared by every block's pools: slot
    tables are identical across blocks (the same virtual rows), so one
    count per page id covers all of them.

    Invariants:

    * page 0 is never allocated — it is the dense-scatter target for
      masked writes (dead slots, shared-prefix prefill rows);
    * a page a slot is about to *write* (``prepare_decode``) always has
      refcount 1 — shared pages are forked first (copy-on-write);
    * a freed page returns to the free list only at refcount 0, so a
      donor's retirement never invalidates a sharer's prefix.
    """

    def __init__(self, plan: PagedPlan, slots: int):
        self.plan = plan
        self.slots = slots
        self.free: list[int] = list(range(plan.n_pages - 1, 0, -1))
        self.refcount = np.zeros(plan.n_pages, np.int32)
        self.table = np.zeros((slots, plan.pages_per_slot), np.int32)
        # True whenever the host table has edits the device copy hasn't
        # seen; ``sync_page_table`` clears it.  Steady-state decode
        # (write row inside an already-owned page) leaves the table
        # untouched, so the per-tick sync becomes a no-op.
        self.dirty = True

    @property
    def free_pages(self) -> int:
        return len(self.free)

    @property
    def used_pages(self) -> int:
        return int((self.refcount > 0).sum())

    def _alloc(self) -> int:
        if not self.free:
            raise RuntimeError(
                f"page pool exhausted ({self.plan.n_pages} pages, "
                f"page_size={self.plan.page_size}) — retire a slot or "
                f"compile with a larger page_pool")
        p = self.free.pop()
        self.refcount[p] = 1
        return p

    def _unref(self, p: int) -> None:
        self.refcount[p] -= 1
        if self.refcount[p] == 0:
            self.free.append(p)

    def can_admit(self, length: int, shared_pages: int = 0) -> bool:
        need = pages_for_len(length, self.plan.page_size) - shared_pages
        return need <= len(self.free)

    def admit(self, slot: int, length: int,
              shared: tuple[int, ...] = ()) -> int:
        """Map ``shared`` donor pages (full-page common prefix, in
        order) into ``slot``'s table, allocate fresh pages for the
        rest of the ``length``-row prompt, and return ``write_from`` —
        the first row the prefill must actually write (= the shared
        row count)."""
        pg = self.plan.page_size
        need = pages_for_len(length, pg)
        shared = tuple(shared)[:need]
        row = np.zeros(self.plan.pages_per_slot, np.int32)
        for i, p in enumerate(shared):
            self.refcount[p] += 1
            row[i] = p
        for i in range(len(shared), need):
            row[i] = self._alloc()
        self.table[slot] = row
        self.dirty = True
        return len(shared) * pg

    def release(self, slot: int) -> None:
        """Retire a slot: unref every mapped page (freed at refcount 0)
        and null the table row so re-admission starts clean."""
        for p in self.table[slot]:
            if p:
                self._unref(int(p))
        self.table[slot] = 0
        self.dirty = True

    def slot_pages(self, slot: int, length: int) -> tuple[int, ...]:
        """The slot's first ``pages_for_len(length)`` page ids — what a
        donor exposes for prefix sharing."""
        n = pages_for_len(length, self.plan.page_size)
        return tuple(int(p) for p in self.table[slot, :n])

    def shared_prefix_pages(self, slot: int, donor_prompt: tuple,
                            prompt: tuple) -> tuple[int, ...]:
        """Donor pages coverable by the common *full-page* prompt
        prefix of ``donor_prompt`` and ``prompt`` (partial pages can't
        be shared — the donor's rows past the common prefix live in
        the same page)."""
        pg = self.plan.page_size
        common = 0
        for a, b in zip(donor_prompt, prompt):
            if a != b:
                break
            common += 1
        return self.slot_pages(slot, (common // pg) * pg)

    def prepare_decode(self, slot: int, pos: int):
        """Make the page receiving the write at ``pos % cache_len``
        writable: allocate it if the table entry is still null, fork it
        (new page, caller copies rows) if shared.  Returns the (src,
        dst) copy a COW fork requires, else None."""
        pg = self.plan.page_size
        idx = (pos % self.plan.cache_len) // pg
        p = int(self.table[slot, idx])
        if p == 0:
            self.table[slot, idx] = self._alloc()
            self.dirty = True
            return None
        if self.refcount[p] > 1:
            fresh = self._alloc()
            self._unref(p)
            self.table[slot, idx] = fresh
            self.dirty = True
            return (p, fresh)
        return None


def paged_pool_regions(pair: ProgramPair) -> list[tuple]:
    """(k_pages, v_pages, k_scale, v_scale) region-id tuples of every
    paged decode op — the buffers a COW fork must copy (scale rids are
    None for float pools)."""
    out = []
    for op in pair.decode.ops:
        if (op.kernel == "decode_attention"
                and op.page_table_region is not None):
            out.append((op.k_cache_region, op.v_cache_region,
                        op.k_scale_region, op.v_scale_region))
    return out


def sync_page_table(state: ProgramState, pair: ProgramPair,
                    pool: PagePool) -> None:
    """Push the host mirror of the page table to the device state (the
    jitted runners read the device copy; all mutation is host-side).
    No-op when the table is unchanged since the last sync — the
    steady-state decode tick transfers nothing."""
    if not pool.dirty:
        return
    state.caches[pair.page_table_region] = jnp.asarray(pool.table)
    pool.dirty = False


def apply_page_copies(state: ProgramState, pair: ProgramPair,
                      copies) -> None:
    """Apply COW forks: device-copy pool page ``src -> dst`` (rows and,
    for int8 pools, the per-page scale) across every block's K and V
    pools.  Runs between jitted calls; each copy is one small
    dynamic-slice update per buffer."""
    if not copies:
        return
    rids = [r for quad in paged_pool_regions(pair) for r in quad
            if r is not None]
    for src, dst in copies:
        for rid in rids:
            buf = state.caches[rid]
            state.caches[rid] = buf.at[dst].set(buf[src])


# --- trace recorder (measured-cost loop, stage 7) ----------------------------------
def _shape_dtype(x) -> list:
    return [list(x.shape), str(jnp.asarray(x).dtype)]


def _op_operands(op: ProgramOp, regions: dict, params,
                 caches: dict | None = None) -> dict:
    """role -> [shape, dtype] for everything the op touches."""
    out: dict[str, list] = {"in": _shape_dtype(regions[op.in_region])}
    for role, rid in (("k", op.k_region), ("v", op.v_region),
                      ("in2", op.in2_region)):
        if rid is not None:
            out[role] = _shape_dtype(regions[rid])
    if op.fuse_bypass and op.bypass_region is not None:
        out["bypass"] = _shape_dtype(regions[op.bypass_region])
    if op.param_key is not None:
        p = _param(params, op.param_key)
        if isinstance(p, dict) and "w" not in p:
            # Family ops (wkv / ssm_scan / moe_dispatch) carry a whole
            # block subtree, not a w/b pair; record the leaf count —
            # these kinds are not rebuildable in isolation (replay
            # raises, the autotuner keeps them identity-only).
            out["param_dict"] = [[len(jax.tree.leaves(p))], "tree"]
        elif isinstance(p, dict):
            out["w"] = _shape_dtype(p["w"])
            if "b" in p:
                out["b"] = _shape_dtype(p["b"])
            out["param_dict"] = [[], "dict"]
        else:
            out["w"] = _shape_dtype(p)
            out["param_dict"] = [[], "array"]
    if op.param_key_b is not None:
        out["b"] = _shape_dtype(_param(params, op.param_key_b))
    if caches is not None and op.k_cache_region is not None:
        out["k_cache"] = _shape_dtype(caches[op.k_cache_region])
        out["v_cache"] = _shape_dtype(caches[op.v_cache_region])
    if caches is not None and op.state_regions:
        for j, rid in enumerate(op.state_regions):
            out[f"state{j}"] = _shape_dtype(caches[rid])
    return out


def _op_schedule(op: ProgramOp) -> dict:
    """The op's resolved schedule decisions, JSON-shaped — every field
    the kernels receive verbatim, so a trace record fully determines
    the dispatch (replay invariant)."""
    d: dict = {
        "strip_storage": op.strip_storage,
        "dataflow": op.dataflow.value if op.dataflow else None,
        "block": list(op.block) if op.block else None,
        "stride": op.stride, "pad": op.pad, "window": op.window,
        "fuse_bias": op.fuse_bias, "fuse_activation": op.fuse_activation,
        "fuse_bypass": op.fuse_bypass, "bypass_first": op.bypass_first,
        "fuse_pool": list(op.fuse_pool) if op.fuse_pool else None,
        "norm_kind": op.norm_kind, "flatten_input": op.flatten_input,
        "transpose_w": op.transpose_w,
    }
    if op.conv_tiling is not None:
        d["conv_tiling"] = dataclasses.asdict(op.conv_tiling)
    if op.attn is not None:
        a = op.attn
        d["attn"] = {"heads": a.heads, "kv_heads": a.kv_heads,
                     "head_dim": a.head_dim, "causal": a.causal,
                     "window": a.window, "rope_theta": a.rope_theta,
                     "block_q": a.block_q, "block_kv": a.block_kv,
                     "page_size": a.page_size, "kv_fold": a.kv_fold}
    return d


@dataclass
class TraceRecord:
    """One executed ProgramOp: identity, resolved schedule, operand
    shapes, modeled cost, and measured wallclock.  ``measured_time_s``
    is the only run-to-run varying field (``static_dict`` drops it);
    everything else is a pure function of the Program + inputs."""
    index: int
    name: str
    kind: str                        # ProgramOp.kernel
    operands: dict
    schedule: dict
    flops: float
    traffic_bytes: float
    modeled_time_s: float
    measured_time_s: float | None = None
    repeats: int = 0
    # runtime operand *values* a replay needs beyond shapes — e.g. the
    # decode slots' positions (kv_len drives the attention work).
    extras: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TraceRecord":
        return cls(**d)

    def static_dict(self) -> dict:
        d = self.to_dict()
        d.pop("measured_time_s")
        d.pop("repeats")
        return d


@dataclass
class ExecutorTrace:
    """A traced Program execution: one TraceRecord per op + the context
    needed to interpret the timings.  Serializes to JSONL (meta header
    line, then one record per line) — the interchange format between
    the executor, ``core/cost.fit_cost_model`` and ``core/autotune``."""
    program: str
    hw: str
    impl: str
    interpret: bool | None
    repeats: int
    records: list = field(default_factory=list)

    def record_dicts(self) -> list[dict]:
        return [r.to_dict() for r in self.records]

    def to_jsonl(self) -> str:
        meta = {"trace_meta": {"program": self.program, "hw": self.hw,
                               "impl": self.impl, "interpret": self.interpret,
                               "repeats": self.repeats}}
        lines = [json.dumps(meta)]
        lines += [json.dumps(d, sort_keys=True) for d in self.record_dicts()]
        return "\n".join(lines) + "\n"

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_jsonl())

    @classmethod
    def from_jsonl(cls, text: str) -> "ExecutorTrace":
        lines = [ln for ln in text.splitlines() if ln.strip()]
        meta = json.loads(lines[0])["trace_meta"]
        recs = [TraceRecord.from_dict(json.loads(ln)) for ln in lines[1:]]
        return cls(records=recs, **meta)

    @classmethod
    def load(cls, path) -> "ExecutorTrace":
        with open(path) as f:
            return cls.from_jsonl(f.read())


def _time_thunk(thunk, repeats: int) -> float:
    """Min-of-``repeats`` wallclock of ``thunk`` with block-until-ready
    (one untimed warmup absorbs tracing/compilation)."""
    jax.block_until_ready(thunk())
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(thunk())
        best = min(best, time.perf_counter() - t0)
    return best


def trace_program(program: Program, params, x: jax.Array, *,
                  impl: str = "auto", interpret: bool | None = None,
                  repeats: int = 3, measure: bool = True,
                  state: ProgramState | None = None,
                  mask: jax.Array | None = None) -> ExecutorTrace:
    """Execute ``program`` op by op, recording each op's resolved
    schedule, operand shapes, modeled cost and measured wallclock.

    Opt-in (the fast path is ``jitted_runner``): ops dispatch eagerly
    so each can be individually blocked on and timed; the per-call
    dispatch overhead is uniform and lands in the calibration's
    ``gamma`` term.  Stateless Programs take (params, x); decode
    Programs additionally need ``state`` (and optional ``mask``), and
    the cache write is timed as part of its ``decode_attention`` op —
    that *is* the op's memory traffic.  ``measure=False`` skips the
    timing loops (schema-only traces, e.g. on CI).
    """
    is_decode = (any(op.kernel == "decode_attention" for op in program.ops)
                 or program.name.endswith(".decode"))
    if is_decode and state is None:
        raise ValueError("decode Programs need state=; see run_decode")
    regions: dict[int, jax.Array] = {program.input_region: x}
    caches = dict(state.caches) if state is not None else None
    pos = state.lengths if state is not None else None
    live = None
    if state is not None:
        live = (jnp.ones(pos.shape, bool) if mask is None
                else jnp.asarray(mask, bool))
    trace = ExecutorTrace(program=program.name, hw=program.hw_name,
                          impl=impl, interpret=interpret,
                          repeats=repeats if measure else 0)
    for op in program.ops:
        src = regions[op.in_region]
        if op.kernel == "decode_attention":
            ck0, cv0 = caches[op.k_cache_region], caches[op.v_cache_region]
            if op.page_table_region is not None:
                quant = op.k_scale_region is not None
                ks0 = caches[op.k_scale_region] if quant else None
                vs0 = caches[op.v_scale_region] if quant else None
                pt0 = caches[op.page_table_region]

                def thunk(op=op, src=src, ck0=ck0, cv0=cv0, ks0=ks0,
                          vs0=vs0, pt0=pt0):
                    return _run_decode_attention_paged(
                        op, src, regions[op.k_region], regions[op.v_region],
                        ck0, cv0, ks0, vs0, pt0, pos, live,
                        impl=impl, interpret=interpret)

                out, ck, cv, ks, vs = thunk()
                if quant:
                    caches[op.k_scale_region] = ks
                    caches[op.v_scale_region] = vs
            else:
                def thunk(op=op, src=src, ck0=ck0, cv0=cv0):
                    return _run_decode_attention(
                        op, src, regions[op.k_region], regions[op.v_region],
                        ck0, cv0, pos, live, impl=impl, interpret=interpret)

                out, ck, cv = thunk()
            caches[op.k_cache_region] = ck
            caches[op.v_cache_region] = cv
        elif op.kernel in _FAMILY_KERNELS:
            # Each call works on a fresh copy of the pre-op cache dict
            # so repeated timing runs are idempotent; the real state
            # advance is applied once from the first call's copy.
            snap = dict(caches) if caches is not None else None

            def thunk(op=op, src=src, snap=snap):
                cc = dict(snap) if snap is not None else None
                res = _run_family_op(op, src, regions, params, cc,
                                     slot=0, live=live, impl=impl,
                                     interpret=interpret)
                return res, cc

            out, cc = thunk()
            if caches is not None:
                caches.update(cc)
        else:
            def thunk(op=op, src=src):
                return _run_op(op, src, regions, params, impl=impl,
                               interpret=interpret,
                               pos=pos if is_decode else None)

            out = thunk()
        regions[op.out_region] = out
        operands = _op_operands(op, regions, params, caches)
        operands["out"] = _shape_dtype(out)
        extras = {}
        if op.kernel == "decode_attention":
            extras = {"pos": [int(p) for p in pos],
                      "live": [bool(b) for b in live]}
        trace.records.append(TraceRecord(
            index=op.index, name=op.name, kind=op.kernel,
            operands=operands, schedule=_op_schedule(op),
            flops=op.flops, traffic_bytes=op.traffic_bytes,
            modeled_time_s=op.exec_time_s,
            measured_time_s=_time_thunk(thunk, repeats) if measure else None,
            repeats=repeats if measure else 0, extras=extras))
    return trace
