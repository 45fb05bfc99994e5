"""Public flash-attention wrapper: schedule-driven blocks, padding,
pallas/reference dispatch, and two differentiable paths:

* ``impl="pallas"``           — Pallas forward (serving path);
* ``impl="pallas_trainable"`` — Pallas forward AND backward (the dq /
  dkv kernels in bwd_kernel.py) under a custom VJP.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..common import resolve_impl
from ...core.hw import TPU_V5E, HardwareModel
from .bwd_kernel import flash_attention_bwd_pallas
from .kernel import flash_attention_pallas
from .ref import flash_ref

__all__ = ["flash_attention", "attention_block_sizes"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_trainable(q, k, v, scale, causal, window, kv_len, block_q,
                     block_kv, interpret):
    out, _ = flash_attention_pallas(
        q, k, v, scale=scale, causal=causal, window=window, kv_len=kv_len,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        return_lse=True)
    return out


def _ft_fwd(q, k, v, scale, causal, window, kv_len, block_q, block_kv,
            interpret):
    out, lse = flash_attention_pallas(
        q, k, v, scale=scale, causal=causal, window=window, kv_len=kv_len,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        return_lse=True)
    return out, (q, k, v, out, lse)


def _ft_bwd(scale, causal, window, kv_len, block_q, block_kv, interpret,
            res, do):
    q, k, v, out, lse = res
    dq, dk, dv = flash_attention_bwd_pallas(
        q, k, v, out, lse, do, scale=scale, causal=causal, window=window,
        kv_len=kv_len, block_q=block_q, block_kv=block_kv,
        interpret=interpret)
    return dq, dk, dv


_flash_trainable.defvjp(_ft_fwd, _ft_bwd)


def attention_block_sizes(Sq: int, Skv: int, D: int, dtype_bytes: int,
                          hw: HardwareModel = TPU_V5E, *,
                          window: int | None = None) -> tuple[int, int]:
    """Pick (block_q, block_kv) so the working set fits the VMEM budget
    (T2 applied to attention).  The decision lives in the compiler
    (core/tiling.py::select_attention_blocks) — one chooser shared by
    this wrapper and the LM Program lowering.  A sliding ``window``
    caps the kv tile (no tile outgrows the span a query can attend)."""
    from ...core.tiling import select_attention_blocks
    return select_attention_blocks(Sq, Skv, D, dtype_bytes, hw,
                                   window=window)


def flash_attention(q, k, v, *, scale: float | None = None,
                    causal: bool = False, window: int | None = None,
                    kv_len=None, impl: str = "auto",
                    block_q: int | None = None, block_kv: int | None = None,
                    hw: HardwareModel = TPU_V5E,
                    interpret: bool | None = None) -> jax.Array:
    """Softmax attention, q (B,Hq,Sq,D), kv (B,Hkv,Skv,D).

    impl:
      "reference" — chunked jnp flash (memory-safe, differentiable);
      "pallas"    — Pallas forward; gradients via the reference VJP
                    (forward-only use is the serving path);
      "auto"      — pallas on TPU else reference.
    """
    # "auto" on the chip: fwd + bwd Pallas kernels; the fwd is
    # identical, so this is safe for inference too.
    impl = resolve_impl(impl, tpu="pallas_trainable")
    D = q.shape[-1]
    scale = scale if scale is not None else D ** -0.5
    if impl == "reference":
        return flash_ref(q, k, v, scale=scale, causal=causal, window=window,
                         kv_len=kv_len)

    B, Hq, Sq, D = q.shape
    Skv = k.shape[2]
    if block_q is None or block_kv is None:
        bq, bkv = attention_block_sizes(Sq, Skv, D, q.dtype.itemsize, hw,
                                        window=window)
        block_q = block_q or bq
        block_kv = block_kv or bkv
    block_q = min(block_q, Sq) if Sq % min(block_q, Sq) == 0 else 128
    # Pad sequences to block multiples; padded keys are masked via kv_len.
    pad_q = (-Sq) % block_q
    pad_kv = (-Skv) % block_kv
    if pad_kv and kv_len is None:
        kv_len = Skv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_kv), (0, 0))) if pad_kv else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_kv), (0, 0))) if pad_kv else v
    if impl == "pallas_trainable":
        out = _flash_trainable(qp, kp, vp, scale, causal, window, kv_len,
                               block_q, block_kv, interpret)
    else:
        out = flash_attention_pallas(qp, kp, vp, scale=scale, causal=causal,
                                     window=window, kv_len=kv_len,
                                     block_q=block_q, block_kv=block_kv,
                                     interpret=interpret)
    return out[:, :, :Sq] if pad_q else out
