"""Schedule-driven tiled matmul Pallas kernel.

The schedule compiler (core/dataflow.py) picks one of three dataflows
per layer; each maps to a distinct grid/BlockSpec arrangement.  All
three share one kernel body with a fused epilogue (bias + activation +
residual bypass — the paper's VMOV-on-writeback, T1/T5):

* MAPS_RESIDENT (paper Kloop)     grid (m, n): the A-slab (bm x K) block
  index ignores n, so the Pallas pipeline keeps it resident across the
  inner n sweep; B streams once per m-tile.
* WEIGHTS_RESIDENT (paper Mloop)  grid (n, m): the B-slab (K x bn) index
  ignores m; A streams once per n-tile.
* OUTPUT_STATIONARY (beyond-paper) grid (m, n, k): both operands tiled;
  f32 accumulator in VMEM scratch, epilogue on the last k step.

Inputs must be pre-padded to block multiples (ops.py does this).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (apply_activation, compiler_params, default_interpret,
                      vmem_scratch)
from ...core.dataflow import Dataflow

__all__ = ["matmul_pallas"]


def _epilogue(acc, bias_ref, bypass_ref, activation, out_dtype):
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    acc = apply_activation(acc, activation)
    if bypass_ref is not None:
        acc = acc + bypass_ref[...].astype(jnp.float32)
    return acc.astype(out_dtype)


def _resident_body(a_ref, b_ref, *rest, activation, out_dtype,
                   has_bias, has_bypass):
    """Single-shot contraction: full K present in both refs."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    bypass_ref = refs.pop(0) if has_bypass else None
    o_ref = refs.pop(0)
    acc = jnp.dot(a_ref[...], b_ref[...],
                  preferred_element_type=jnp.float32)
    o_ref[...] = _epilogue(acc, bias_ref, bypass_ref, activation, out_dtype)


def _os_body(a_ref, b_ref, *rest, activation, out_dtype, has_bias,
             has_bypass, k_axis):
    """Output-stationary: accumulate over the k grid dim in scratch."""
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    bypass_ref = refs.pop(0) if has_bypass else None
    o_ref = refs.pop(0)
    acc_ref = refs.pop(0)
    k = pl.program_id(k_axis)
    nk = pl.num_programs(k_axis)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _done():
        o_ref[...] = _epilogue(acc_ref[...], bias_ref, bypass_ref,
                               activation, out_dtype)


def matmul_pallas(a: jax.Array, b: jax.Array, *,
                  dataflow: Dataflow = Dataflow.OUTPUT_STATIONARY,
                  block: tuple[int, int, int],
                  bias: jax.Array | None = None,
                  activation: str | None = None,
                  bypass: jax.Array | None = None,
                  out_dtype=None,
                  interpret: bool | None = None) -> jax.Array:
    """2D matmul (M,K)x(K,N) with fused epilogue.  Shapes must already be
    padded to the block multiples implied by ``dataflow``/``block``."""
    if interpret is None:
        interpret = default_interpret()
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bk, bn = block
    out_dtype = out_dtype or a.dtype
    has_bias = bias is not None
    has_bypass = bypass is not None
    out_shape = jax.ShapeDtypeStruct((M, N), out_dtype)

    if dataflow is Dataflow.MAPS_RESIDENT:
        assert M % bm == 0 and N % bn == 0, (a.shape, b.shape, block)
        grid = (M // bm, N // bn)                      # m outer, n inner
        a_spec = pl.BlockSpec((bm, K), lambda m, n: (m, 0))   # resident
        b_spec = pl.BlockSpec((K, bn), lambda m, n: (0, n))   # streamed
        o_spec = pl.BlockSpec((bm, bn), lambda m, n: (m, n))
        extra_specs = []
        if has_bias:
            extra_specs.append(pl.BlockSpec((1, bn), lambda m, n: (0, n)))
        if has_bypass:
            extra_specs.append(pl.BlockSpec((bm, bn), lambda m, n: (m, n)))
        body = functools.partial(_resident_body, activation=activation,
                                 out_dtype=out_dtype, has_bias=has_bias,
                                 has_bypass=has_bypass)
        scratch = []
        semantics = ("arbitrary", "arbitrary")
    elif dataflow is Dataflow.WEIGHTS_RESIDENT:
        assert M % bm == 0 and N % bn == 0, (a.shape, b.shape, block)
        grid = (N // bn, M // bm)                      # n outer, m inner
        a_spec = pl.BlockSpec((bm, K), lambda n, m: (m, 0))   # streamed
        b_spec = pl.BlockSpec((K, bn), lambda n, m: (0, n))   # resident
        o_spec = pl.BlockSpec((bm, bn), lambda n, m: (m, n))
        extra_specs = []
        if has_bias:
            extra_specs.append(pl.BlockSpec((1, bn), lambda n, m: (0, n)))
        if has_bypass:
            extra_specs.append(pl.BlockSpec((bm, bn), lambda n, m: (m, n)))
        body = functools.partial(_resident_body, activation=activation,
                                 out_dtype=out_dtype, has_bias=has_bias,
                                 has_bypass=has_bypass)
        scratch = []
        semantics = ("arbitrary", "arbitrary")
    else:  # OUTPUT_STATIONARY
        assert M % bm == 0 and N % bn == 0 and K % bk == 0, \
            (a.shape, b.shape, block)
        grid = (M // bm, N // bn, K // bk)             # k innermost
        a_spec = pl.BlockSpec((bm, bk), lambda m, n, k: (m, k))
        b_spec = pl.BlockSpec((bk, bn), lambda m, n, k: (k, n))
        o_spec = pl.BlockSpec((bm, bn), lambda m, n, k: (m, n))
        extra_specs = []
        if has_bias:
            extra_specs.append(pl.BlockSpec((1, bn), lambda m, n, k: (0, n)))
        if has_bypass:
            extra_specs.append(
                pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)))
        body = functools.partial(_os_body, activation=activation,
                                 out_dtype=out_dtype, has_bias=has_bias,
                                 has_bypass=has_bypass, k_axis=2)
        scratch = [vmem_scratch((bm, bn), jnp.float32)]
        semantics = ("parallel", "parallel", "arbitrary")

    operands = [a, b]
    if has_bias:
        operands.append(bias.reshape(1, N))
    if has_bypass:
        operands.append(bypass)

    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=[a_spec, b_spec] + extra_specs,
        out_specs=o_spec,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        compiler_params=compiler_params(semantics),
    )(*operands)
