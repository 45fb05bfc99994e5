"""KV-cache decode attention Pallas kernel.

One new query token per sequence against a long KV cache — the
bandwidth-bound serving hot spot (every cache byte is read once per
step, arithmetic intensity ~= 1 MAC/byte).  The schedule compiler's job
here is purely T2/T4: size the kv block to VMEM and keep the streams
busy; there is no loop-order freedom (the cache is the only big
operand).

Grid: (B * Hq, S / bkv), kv innermost with running-softmax scratch.
GQA folded into the KV index map as in flash_attention.

Lane-dense cache: the cache arrives as it rests in its persistent
region, (B, Hkv, S / f, f * D) with ``f`` consecutive rows folded into
one row of ``f * D`` lanes (``core/regions.py::kv_fold``; f = 1 is
plain head-major).  Each grid step streams a (bkv / f, f * D) block and
scores it against ``f`` query rows at once: query row ``p`` holds q in
lane group ``p`` and zeros elsewhere, so score ``(p, j)`` is real cache
row ``f * j + p``.  The f rows share one running max and sum; lane
group ``p`` of accumulator row ``p`` is that parity's share of the
output, and the groups are summed by lane rotation when the last block
has been read.

Ring-cache semantics: the per-sequence ``kv_len`` (see
ops.py::ring_kv_len) bounds the valid rows of a rolling cache — blocks
whose start is past ``kv_len`` are skipped entirely (``sk0 < kv_len``
guard, so a window-sized cache streams only window bytes) and the tail
block masks per-row.  The kernel never reorders rows; the wrapped ring
layout is handled by softmax's permutation invariance.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import compiler_params, default_interpret, pltpu, vmem_scratch

__all__ = ["decode_attention_pallas", "paged_decode_attention_pallas"]

NEG_INF = -1e30


def _online_softmax_step(q, k, v, valid, m_ref, l_ref, acc_ref, scale):
    """Fold one kv block into the running softmax state.  q: (f, W)
    query rows; k, v: (R, W) f32; valid: (f, R) row mask.  One max and
    one sum are shared by all f query rows."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_ref[...]
    m_cur = jnp.max(jnp.max(s, axis=-1, keepdims=True), axis=0,
                    keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
    alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])
    p = jnp.exp(s - m_new[:, :1])
    p_sum = jnp.sum(jnp.sum(p, axis=-1, keepdims=True), axis=0,
                    keepdims=True)
    l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(p_sum, l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _emit(o_ref, l_ref, acc_ref, fold: int = 1):
    """Normalise and store the (1, D) output.  With f > 1 the output is
    lane group ``p`` of accumulator row ``p``, summed over ``p``: mask
    the other groups, sum the rows, then add the f - 1 lane rotations
    so that lanes [0, D) hold the sum of every group."""
    acc = acc_ref[...]
    if fold > 1:
        W = acc.shape[1]
        D = W // fold
        lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        acc = jnp.sum(jnp.where(lane // D == row, acc, 0.0), axis=0,
                      keepdims=True)
        acc = acc + sum(pltpu.roll(acc, p * D, 1) for p in range(1, fold))
        acc = acc[:, :D]
    l = jnp.maximum(l_ref[:, :1], 1e-30)
    o_ref[0] = (acc / l).astype(o_ref.dtype)


def _body(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
          scale, bkv, Hq, fold):
    kb = pl.program_id(1)
    nkv = pl.num_programs(1)

    @pl.when(kb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    kv_len = len_ref[pl.program_id(0) // Hq]     # scalar-prefetched (SMEM)
    sk0 = kb * bkv

    @pl.when(sk0 < kv_len)
    def _compute():
        shape = (fold, bkv // fold)
        ki = (sk0 + fold * jax.lax.broadcasted_iota(jnp.int32, shape, 1)
              + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
        _online_softmax_step(q_ref[0].astype(jnp.float32),
                             k_ref[0].astype(jnp.float32),
                             v_ref[0].astype(jnp.float32), ki < kv_len,
                             m_ref, l_ref, acc_ref, scale)

    @pl.when(kb == nkv - 1)
    def _finish():
        _emit(o_ref, l_ref, acc_ref, fold)


def _state_scratch(rows, W):
    return [vmem_scratch((1, 128), jnp.float32),
            vmem_scratch((1, 128), jnp.float32),
            vmem_scratch((rows, W), jnp.float32)]


def decode_attention_pallas(q, k, v, kv_len, *, scale: float,
                            block_kv: int = 1024, fold: int = 1,
                            interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, D); k, v: (B, Hkv, S / fold, fold * D), the lane-dense
    cache as it rests (fold 1: plain (B, Hkv, S, D)); kv_len: (B,)
    int32 valid real rows, handed to the kernel as a scalar-prefetch
    operand (it lives in SMEM).  ``block_kv`` counts real rows."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, D = q.shape
    _, Hkv, Sf, W = k.shape
    assert W == fold * D, (k.shape, fold, D)
    S = Sf * fold
    group = Hq // Hkv
    bkv = min(block_kv, S)
    assert S % bkv == 0 and bkv % fold == 0

    qf = q.reshape(B * Hq, 1, D)
    if fold > 1:
        # Query row p: q in lane group p, zeros elsewhere.
        groups = (jnp.arange(W)[None] // D) == jnp.arange(fold)[:, None]
        qf = jnp.where(groups[None], jnp.tile(qf, (1, 1, fold)), 0)
    kf = k.reshape(B * Hkv, Sf, W)
    vf = v.reshape(B * Hkv, Sf, W)

    def kv_map(h, kb, lens):
        return ((h // Hq) * Hkv + (h % Hq) // group, kb, 0)

    kv_block = pl.BlockSpec((1, bkv // fold, W), kv_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B * Hq, S // bkv),
        in_specs=[pl.BlockSpec((1, fold, W), lambda h, kb, lens: (h, 0, 0)),
                  kv_block, kv_block],
        out_specs=pl.BlockSpec((1, 1, D), lambda h, kb, lens: (h, 0, 0)),
        scratch_shapes=_state_scratch(fold, W))
    out = pl.pallas_call(
        functools.partial(_body, scale=scale, bkv=bkv, Hq=Hq, fold=fold),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, D), q.dtype),
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "arbitrary")),
    )(kv_len.astype(jnp.int32), qf, kf, vf)
    return out.reshape(B, Hq, D)


def _paged_body(len_ref, pt_ref, *refs, scale, page_size, Hq, Hkv, quant):
    """One (sequence*head, page) grid step of paged decode attention.

    The kv block IS the page: the page table and the ring extents are
    scalar-prefetched into SMEM, and the page this step reads is
    gathered with a dynamic index into the whole-pool ref.  Quantized
    pools carry per-page dequant scales, also in SMEM, applied right
    after the load."""
    if quant:
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    h = pl.program_id(0)
    kb = pl.program_id(1)
    nkv = pl.num_programs(1)
    b = h // Hq
    kvh = (h % Hq) // (Hq // Hkv)

    @pl.when(kb == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    kv_len = len_ref[b]
    sk0 = kb * page_size

    @pl.when(sk0 < kv_len)
    def _compute():
        page = pt_ref[b, kb]
        k = _head_rows(k_ref[page], kvh, Hkv)                    # (pg, D)
        v = _head_rows(v_ref[page], kvh, Hkv)
        if quant:
            k = k * ks_ref[page]
            v = v * vs_ref[page]
        ki = sk0 + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        _online_softmax_step(q_ref[0].astype(jnp.float32), k, v,
                             ki < kv_len, m_ref, l_ref, acc_ref, scale)

    @pl.when(kb == nkv - 1)
    def _finish():
        _emit(o_ref, l_ref, acc_ref)


def _head_rows(page, kvh, Hkv):
    """Rows of KV head ``kvh`` (a runtime index) from one loaded page
    (page_size, Hkv, D), as f32 (page_size, D).  Mosaic cannot index the
    sublane (head) dim at a runtime offset, so each head is sliced at a
    static one and the runtime index selects among them."""
    page = page.astype(jnp.float32)
    rows = page[:, 0, :]
    for j in range(1, Hkv):
        rows = jnp.where(kvh == j, page[:, j, :], rows)
    return rows


def paged_decode_attention_pallas(q, k_pages, v_pages, page_table, kv_len, *,
                                  scale: float,
                                  k_scale=None, v_scale=None,
                                  interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, D); k_pages, v_pages: (n_pages, page_size, Hkv, D)
    pools; page_table: (B, pages_per_slot) int32; kv_len: (B,) int32
    ring extents.  k_scale / v_scale: (n_pages,) float32 per-page
    dequant scales for int8 pools, or None for float pools.

    Grid (B * Hq, pages_per_slot).  kv_len, the page table and the
    scales are scalar-prefetch operands; the pools ride along whole
    (their index map is constant), so the pool must fit VMEM.
    block_kv == page_size by construction (core/tiling.py pins it)."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, D = q.shape
    n_pages, page_size, Hkv, _ = k_pages.shape
    pages_per_slot = page_table.shape[1]
    quant = k_scale is not None

    prefetch = [kv_len.astype(jnp.int32), page_table.astype(jnp.int32)]
    if quant:
        prefetch += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    n_pf = len(prefetch)
    whole_pool = pl.BlockSpec((n_pages, page_size, Hkv, D),
                              lambda h, kb, *_: (0, 0, 0, 0))
    row = pl.BlockSpec((1, 1, D), lambda h, kb, *_: (h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_pf, grid=(B * Hq, pages_per_slot),
        in_specs=[row, whole_pool, whole_pool], out_specs=row,
        scratch_shapes=_state_scratch(1, D))
    out = pl.pallas_call(
        functools.partial(_paged_body, scale=scale, page_size=page_size,
                          Hq=Hq, Hkv=Hkv, quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * Hq, 1, D), q.dtype),
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "arbitrary")),
    )(*prefetch, q.reshape(B * Hq, 1, D), k_pages, v_pages)
    return out.reshape(B, Hq, D)
