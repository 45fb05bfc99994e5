"""Flash-attention backward Pallas kernels (two-pass).

* ``_dkv``: grid (B*Hkv, Skv/bkv, G*Sq/bq) — for each kv block,
  accumulate dK/dV in VMEM scratch while streaming every (group, q
  block) of its GQA group; the group sum falls out of the sequential
  inner axis.
* ``_dq``:  grid (B*Hq, Sq/bq, Skv/bkv) — accumulate dQ per q block
  while streaming kv blocks (KV indexed through the GQA head map, as in
  the forward kernel).

Both recompute p^T = exp(k q^T * scale - lse) from the forward's saved
logsumexp, transposed so the per-query rows (lse and ``delta =
rowsum(dO * O)``, precomputed below) broadcast along lanes.
Masking (causal / window / kv_len) matches the forward kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import compiler_params, default_interpret, vmem_scratch

__all__ = ["flash_attention_bwd_pallas"]

NEG_INF = -1e30


def _scores_t(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, *, scale,
              causal, window, kv_len, sq0, sk0, bq, bkv):
    """Recompute the transposed probabilities and score gradients.

    Everything is laid out (bkv, bq): kv rows on sublanes, queries on
    lanes, so the saved per-query logsumexp and delta broadcast as the
    lane-dense (1, bq) rows they are stored as.  Returns (q, do, p^T,
    ds^T) with q/do as f32 (bq, D)."""
    q = q_ref[0].astype(jnp.float32)                     # (bq, D)
    k = k_ref[0].astype(jnp.float32)                     # (bkv, D)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)                   # (bq, D)
    lse = lse_ref[0]                                     # (1, bq)
    delta = dl_ref[0]                                    # (1, bq)
    st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    ki = sk0 + jax.lax.broadcasted_iota(jnp.int32, (bkv, bq), 0)
    qi = sq0 + jax.lax.broadcasted_iota(jnp.int32, (bkv, bq), 1)
    ok = jnp.ones((bkv, bq), jnp.bool_)
    if causal:
        ok &= ki <= qi
    if window is not None:
        ok &= ki > qi - window
    if kv_len is not None:
        ok &= ki < kv_len
    st = jnp.where(ok, st, NEG_INF)
    pt = jnp.exp(st - lse)                               # (bkv, bq)
    dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dst = pt * (dpt - delta) * scale
    return q, do, pt, dst


def _dkv_body(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
              dk_ref, dv_ref, dka_ref, dva_ref, *,
              scale, causal, window, kv_len, bq, bkv, nq):
    kb = pl.program_id(1)
    inner = pl.program_id(2)
    n_inner = pl.num_programs(2)
    qb = inner % nq

    @pl.when(inner == 0)
    def _init():
        dka_ref[...] = jnp.zeros_like(dka_ref)
        dva_ref[...] = jnp.zeros_like(dva_ref)

    sq0 = qb * bq
    sk0 = kb * bkv
    run = jnp.bool_(True)
    if causal:
        run &= sk0 <= sq0 + bq - 1
    if window is not None:
        run &= sk0 + bkv - 1 > sq0 - window

    @pl.when(run)
    def _acc():
        q, do, pt, dst = _scores_t(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, scale=scale,
            causal=causal, window=window, kv_len=kv_len, sq0=sq0, sk0=sk0,
            bq=bq, bkv=bkv)
        dva_ref[...] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # p^T dO
        dka_ref[...] += jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # ds^T q

    @pl.when(inner == n_inner - 1)
    def _emit():
        dk_ref[0] = dka_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dva_ref[...].astype(dv_ref.dtype)


def _dq_body(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref,
             dqa_ref, *, scale, causal, window, kv_len, bq, bkv):
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    nkv = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        dqa_ref[...] = jnp.zeros_like(dqa_ref)

    sq0 = qb * bq
    sk0 = kb * bkv
    run = jnp.bool_(True)
    if causal:
        run &= sk0 <= sq0 + bq - 1
    if window is not None:
        run &= sk0 + bkv - 1 > sq0 - window

    @pl.when(run)
    def _acc():
        _, _, _, dst = _scores_t(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, scale=scale,
            causal=causal, window=window, kv_len=kv_len, sq0=sq0, sk0=sk0,
            bq=bq, bkv=bkv)
        dqa_ref[...] += jax.lax.dot_general(
            dst, k_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # ds k

    @pl.when(kb == nkv - 1)
    def _emit():
        dq_ref[0] = dqa_ref[...].astype(dq_ref.dtype)


def flash_attention_bwd_pallas(q, k, v, out, lse, do, *, scale: float,
                               causal: bool, window: int | None,
                               kv_len: int | None, block_q: int = 512,
                               block_kv: int = 512,
                               interpret: bool | None = None):
    """Returns (dq, dk, dv).  Shapes as the forward kernel; Sq/Skv must
    be multiples of the block sizes (ops.py pads)."""
    if interpret is None:
        interpret = default_interpret()
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    assert Sq % bq == 0 and Skv % bkv == 0
    nq = Sq // bq

    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Skv, D)
    vf = v.reshape(B * Hkv, Skv, D)
    dof = do.reshape(B * Hq, Sq, D)
    # Per-query rows stay lane-dense: (B*Hq, 1, Sq), blocked (1, 1, bq).
    lsef = lse.astype(jnp.float32).reshape(B * Hq, 1, Sq)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * Hq, 1, Sq)
    params = compiler_params(("parallel", "arbitrary", "arbitrary"))

    # -- dk / dv: per kv head, inner axis sweeps (group, q block) -------------
    def qhead(h, kb, inner):
        return ((h // Hkv) * Hq + (h % Hkv) * G + inner // nq,
                inner % nq, 0)

    def qrow(h, kb, inner):
        hq, qb, _ = qhead(h, kb, inner)
        return (hq, 0, qb)

    body = functools.partial(_dkv_body, scale=scale, causal=causal,
                             window=window, kv_len=kv_len, bq=bq,
                             bkv=bkv, nq=nq)
    dk, dv = pl.pallas_call(
        body,
        grid=(B * Hkv, Skv // bkv, G * nq),
        in_specs=[
            pl.BlockSpec((1, bq, D), qhead),
            pl.BlockSpec((1, bkv, D), lambda h, kb, i: (h, kb, 0)),
            pl.BlockSpec((1, bkv, D), lambda h, kb, i: (h, kb, 0)),
            pl.BlockSpec((1, bq, D), qhead),
            pl.BlockSpec((1, 1, bq), qrow),
            pl.BlockSpec((1, 1, bq), qrow),
        ],
        out_specs=[pl.BlockSpec((1, bkv, D), lambda h, kb, i: (h, kb, 0)),
                   pl.BlockSpec((1, bkv, D), lambda h, kb, i: (h, kb, 0))],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, Skv, D), k.dtype),
                   jax.ShapeDtypeStruct((B * Hkv, Skv, D), v.dtype)],
        scratch_shapes=[vmem_scratch((bkv, D), jnp.float32),
                        vmem_scratch((bkv, D), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(qf, kf, vf, dof, lsef, delta)

    # -- dq: per q head, kv innermost ------------------------------------------
    def kvmap(h, qb, kb):
        return ((h // Hq) * Hkv + (h % Hq) // G, kb, 0)

    body = functools.partial(_dq_body, scale=scale, causal=causal,
                             window=window, kv_len=kv_len, bq=bq, bkv=bkv)
    dq = pl.pallas_call(
        body,
        grid=(B * Hq, nq, Skv // bkv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, qb, kb: (h, qb, 0)),
            pl.BlockSpec((1, bkv, D), kvmap),
            pl.BlockSpec((1, bkv, D), kvmap),
            pl.BlockSpec((1, bq, D), lambda h, qb, kb: (h, qb, 0)),
            pl.BlockSpec((1, 1, bq), lambda h, qb, kb: (h, 0, qb)),
            pl.BlockSpec((1, 1, bq), lambda h, qb, kb: (h, 0, qb)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, qb, kb: (h, qb, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[vmem_scratch((bq, D), jnp.float32)],
        interpret=interpret,
        compiler_params=params,
    )(qf, kf, vf, dof, lsef, delta)

    return (dq.reshape(B, Hq, Sq, D), dk.reshape(B, Hkv, Skv, D),
            dv.reshape(B, Hkv, Skv, D))
