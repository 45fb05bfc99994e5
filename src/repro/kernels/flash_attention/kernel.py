"""Flash-attention forward Pallas kernel (GQA, causal, sliding window).

Grid: (B * Hq, Sq/bq, Skv/bkv) — kv innermost, running softmax state in
VMEM scratch carried across kv steps (TPU grid iterates sequentially, so
scratch persists).  The KV BlockSpec index map folds the GQA head
mapping (q head -> kv head = h // group), so repeated KV heads are never
materialized — the bandwidth saving the schedule compiler counts on.

Block sizes come from core/tiling.py via ops.py; the working set is
q(bq,D) + k(bkv,D) + v(bkv,D) (double-buffered) + acc(bq,D) f32.
Fully-masked kv blocks are skipped with pl.when (compute skip; the
prefetch still streams them — the grid-restriction optimization is
recorded as future work in EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import compiler_params, default_interpret, vmem_scratch

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _body(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
          scale, causal, window, bq, bkv, kv_len):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sq0 = qb * bq
    sk0 = kb * bkv

    # Full-block skip test (static per (qb, kb) only through program ids).
    run = jnp.bool_(True)
    if causal:
        run &= sk0 <= sq0 + bq - 1
    if window is not None:
        run &= sk0 + bkv - 1 > sq0 - window

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (bq, D)
        k = k_ref[0].astype(jnp.float32)                 # (bkv, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qi = sq0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        ki = sk0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        ok = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            ok &= ki <= qi
        if window is not None:
            ok &= ki > qi - window
        if kv_len is not None:
            ok &= ki < kv_len
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_ref[...]                              # (bq, 128)
        m_cur = jnp.max(s, axis=-1, keepdims=True)       # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_cur, m_prev.shape))
        alpha = jnp.exp(m_prev[:, :1] - m_new[:, :1])    # (bq, 1)
        p = jnp.exp(s - m_new[:, :1])
        l_ref[...] = l_ref[...] * alpha + jnp.broadcast_to(
            p.sum(axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kb == nkv - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)               # (bq, 128)
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        # m and l are lane-replicated columns; one transpose turns the
        # logsumexp into the lane-dense (1, bq) row the lse block holds.
        lse_ref[0] = jnp.transpose(m_ref[...] + jnp.log(l))[:1]


def flash_attention_pallas(q, k, v, *, scale: float, causal: bool,
                           window: int | None, kv_len: int | None,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool | None = None,
                           return_lse: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D).  Sq % block_q == 0 and
    Skv % block_kv == 0 (ops.py pads).  ``return_lse`` additionally
    returns the per-row logsumexp (B, Hq, Sq) for the backward pass."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)
    grid = (B * Hq, Sq // bq, Skv // bkv)

    def kv_head(h, qb, kb):
        return ((h // Hq) * Hkv + (h % Hq) // group, kb, 0)

    q_spec = pl.BlockSpec((1, bq, D), lambda h, qb, kb: (h, qb, 0))
    k_spec = pl.BlockSpec((1, bkv, D), kv_head)
    v_spec = pl.BlockSpec((1, bkv, D), kv_head)
    o_spec = pl.BlockSpec((1, bq, D), lambda h, qb, kb: (h, qb, 0))
    lse_spec = pl.BlockSpec((1, 1, bq), lambda h, qb, kb: (h, 0, qb))

    body = functools.partial(_body, scale=scale, causal=causal,
                             window=window, bq=bq, bkv=bkv, kv_len=kv_len)
    out, lse = pl.pallas_call(
        body,
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
                   jax.ShapeDtypeStruct((B * Hq, 1, Sq), jnp.float32)],
        scratch_shapes=[vmem_scratch((bq, 128), jnp.float32),
                        vmem_scratch((bq, 128), jnp.float32),
                        vmem_scratch((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "arbitrary",
                                         "arbitrary")),
    )(qf, kf, vf)
    out = out.reshape(B, Hq, Sq, D)
    if return_lse:
        return out, lse.reshape(B, Hq, Sq)
    return out
