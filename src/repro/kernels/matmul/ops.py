"""Public jit'd wrapper for the scheduled matmul.

Handles schedule lookup (tiling + dataflow from core/), padding to block
multiples, leading-batch-dim folding, and the pallas/reference dispatch
(Pallas on TPU or under interpret=True; pure-jnp reference elsewhere,
e.g. inside the CPU dry-run where Mosaic is unavailable).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import pad_to, resolve_impl, unpad
from ...core.dataflow import Dataflow, choose_matmul_dataflow
from ...core.hw import TPU_V5E, HardwareModel
from .kernel import matmul_pallas
from .ref import matmul_ref

__all__ = ["matmul", "scheduled_matmul"]


def _fold(a: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = a.shape[:-1]
    return a.reshape(-1, a.shape[-1]), lead


def matmul(a: jax.Array, b: jax.Array, *,
           bias: jax.Array | None = None,
           activation: str | None = None,
           bypass: jax.Array | None = None,
           out_dtype=None,
           impl: str = "auto",
           dataflow: Dataflow | None = None,
           block: tuple[int, int, int] | None = None,
           hw: HardwareModel = TPU_V5E,
           interpret: bool | None = None) -> jax.Array:
    """``epilogue(a @ b)`` with schedule-driven tiling.

    a: (..., K); b: (K, N); bias: (N,); bypass: broadcastable to out.
    impl: "auto" | "pallas" | "reference".
    """
    impl = resolve_impl(impl)
    if impl == "reference":
        return matmul_ref(a, b, bias=bias, activation=activation,
                          bypass=bypass, out_dtype=out_dtype)

    a2, lead = _fold(a)
    M, K = a2.shape
    N = b.shape[-1]
    if dataflow is None or block is None:
        dec = choose_matmul_dataflow(M, K, N, a.dtype.itemsize, hw)
        dataflow = dataflow or dec.dataflow
        block = block or (dec.tiling.bm, dec.tiling.bk, dec.tiling.bn)
    bm, bk, bn = block
    bm, bn = min(bm, _ceil_mult(M, 128)), min(bn, _ceil_mult(N, 128))
    bk = min(bk, _ceil_mult(K, 128))
    block = (bm, bk, bn)

    kpad = bk if dataflow is Dataflow.OUTPUT_STATIONARY else 128
    a_p = pad_to(a2, (bm, kpad))
    b_p = pad_to(b, (kpad, bn))
    bypass_p = None
    if bypass is not None:
        bypass_p = pad_to(jnp.broadcast_to(bypass.reshape(M, N), (M, N)),
                          (bm, bn))
    bias_p = pad_to(bias, (bn,)) if bias is not None else None

    out = matmul_pallas(a_p, b_p, dataflow=dataflow, block=block,
                        bias=bias_p, activation=activation,
                        bypass=bypass_p, out_dtype=out_dtype or a.dtype,
                        interpret=interpret)
    out = unpad(out, (M, N))
    return out.reshape(*lead, N)


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def scheduled_matmul(schedule, a: jax.Array, b: jax.Array, **kw) -> jax.Array:
    """Run a matmul under a precomputed ``LayerSchedule``."""
    return matmul(a, b, dataflow=schedule.dataflow, block=schedule.block,
                  activation=schedule.fuse_activation, **kw)
