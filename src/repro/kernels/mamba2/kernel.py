"""Chunked Mamba2 SSD scan Pallas kernel.

TPU adaptation of the SSD block decomposition: the sequence is split
into chunks of Q steps; within a chunk the recurrence is a masked-decay
matmul (MXU work), across chunks a small (N, P) state is carried in VMEM
scratch — the grid's chunk axis is sequential, so scratch persists.
This turns a length-L scan into L/Q matmul tiles, which is exactly the
paper's T2 move: batch enough contiguous MAC work ("traces") per tile to
hide the bookkeeping.

Numerically safe: A < 0 and dt >= 0 make every exponent non-positive.

Grid: (B*H, L/Q).  B/C are shared across heads (single group) and
indexed through bh -> batch maps, so they stream once per batch, not per
head — the Mloop/Kloop reasoning applied to the SSM operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import compiler_params, default_interpret, pltpu, vmem_scratch

__all__ = ["mamba2_scan_pallas"]


def _body(a_ref, x_ref, dt_ref, b_ref, c_ref, h0_ref,
          y_ref, hout_ref, h_ref, *, Q, H):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0].astype(jnp.float32)        # (1, Q), lane-dense row
    A = a_ref[pl.program_id(0) % H]           # scalar (SMEM)
    Bm = b_ref[0].astype(jnp.float32)         # (Q, N)
    Cm = c_ref[0].astype(jnp.float32)         # (Q, N)

    # t indexes sublanes (output step), s lanes (source step).  The
    # inclusive cumsum of a = A*dt, and dt as a column, are masked
    # reductions of the row: Mosaic has no cumsum or row->column
    # transpose at these shapes.
    t_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    a = A * dt                                # (1, Q) <= 0
    dt_col = jnp.sum(jnp.where(s_i == t_i, dt, 0.0), axis=1,
                     keepdims=True)           # (Q, 1)
    cum_col = jnp.sum(jnp.where(s_i <= t_i, a, 0.0), axis=1,
                      keepdims=True)          # (Q, 1): cum[t]
    cum_row = jnp.sum(jnp.where(t_i <= s_i, A * dt_col, 0.0), axis=0,
                      keepdims=True)          # (1, Q): cum[s]
    total = jnp.sum(a, axis=1, keepdims=True)  # (1, 1) = cum[-1]

    # Intra-chunk: masked decay attention on the MXU.
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    dec = jnp.exp(cum_col - cum_row)
    S = jnp.where(s_i <= t_i, CB * dec, 0.0) * dt
    y = jax.lax.dot_general(S, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # Inter-chunk: contribution of the carried state.
    h_prev = h_ref[...]                       # (N, P)
    y += jnp.exp(cum_col) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    # State update.
    w = jnp.exp(total - cum_col) * dt_col     # (Q, 1)
    h_ref[...] = h_prev * jnp.exp(total) + jax.lax.dot_general(
        Bm * w, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...].astype(hout_ref.dtype)


def mamba2_scan_pallas(x, dt, A, B, C, *, h0=None, chunk: int = 256,
                       interpret: bool | None = None):
    """x: (Bt, L, H, P); dt: (Bt, L, H); A: (H,); B, C: (Bt, L, N).
    L must be a multiple of the chunk (ops.py pads).  Returns (y (Bt, L,
    H, P), h_final (Bt, H, N, P))."""
    if interpret is None:
        interpret = default_interpret()
    Bt, L, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, L)
    assert L % Q == 0, (L, Q)

    xf = jnp.moveaxis(x, 2, 1).reshape(Bt * H, L, P)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(Bt * H, 1, L)
    h0f = (h0.reshape(Bt * H, N, P) if h0 is not None
           else jnp.zeros((Bt * H, N, P), jnp.float32))

    # A rides in SMEM as a scalar-prefetch operand (index maps take it
    # as a trailing argument).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(Bt * H, L // Q),
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, c, a: (bh, c, 0)),
            pl.BlockSpec((1, 1, Q), lambda bh, c, a: (bh, 0, c)),
            pl.BlockSpec((1, Q, N), lambda bh, c, a: (bh // H, c, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, c, a: (bh // H, c, 0)),
            pl.BlockSpec((1, N, P), lambda bh, c, a: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, c, a: (bh, c, 0)),
            pl.BlockSpec((1, N, P), lambda bh, c, a: (bh, 0, 0)),
        ],
        scratch_shapes=[vmem_scratch((N, P), jnp.float32)])
    y, h_fin = pl.pallas_call(
        functools.partial(_body, Q=Q, H=H),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Bt * H, L, P), x.dtype),
            jax.ShapeDtypeStruct((Bt * H, N, P), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "arbitrary")),
    )(A.astype(jnp.float32), xf, dtf, B, C, h0f)
    y = jnp.moveaxis(y.reshape(Bt, H, L, P), 1, 2)
    return y, h_fin.reshape(Bt, H, N, P)
