"""RWKV6 WKV recurrence Pallas kernel.

The recurrence is elementwise-decay + rank-1 update — inherently
sequential in t with O(D^2) state.  The TPU-native version keeps the
(D, D) state resident in VMEM for a whole (batch, head) stream and
walks the sequence in chunks: the chunk's r/k/v/w tiles are loaded once
(T2), the time loop runs entirely out of VMEM/VREGs.  This is the
bandwidth-optimal layout — every HBM byte is touched exactly once —
which is what matters for an op with arithmetic intensity ~2 FLOP/byte.

(A chunked matmul reformulation that shifts work onto the MXU is the
§Perf extension; see EXPERIMENTS.md.)

Grid: (B*H, L/Q), sequential chunk axis, state in scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import compiler_params, default_interpret, vmem_scratch

__all__ = ["wkv6_pallas"]


_G = 8    # time steps per aligned row group (the f32 sublane tile)


def _body(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sout_ref,
          s_ref, *, Q):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0].astype(jnp.float32)                       # (D, 1)

    def group(g, S):
        # Load _G steps at a sublane-aligned offset; k, r and w enter
        # the update as columns (the state's key axis is its sublane
        # axis), so their tiles are transposed once per group.
        t0 = pl.multiple_of(g * _G, _G)
        rows = pl.ds(t0, _G)
        rT = r_ref[0, rows].astype(jnp.float32).T          # (D, G)
        kT = k_ref[0, rows].astype(jnp.float32).T
        wT = w_ref[0, rows].astype(jnp.float32).T
        v = v_ref[0, rows].astype(jnp.float32)             # (G, D)
        ys = []
        for i in range(_G):
            kv = kT[:, i:i + 1] * v[i:i + 1]               # (D, D)
            ys.append(jnp.sum(rT[:, i:i + 1] * (S + u * kv), axis=0,
                              keepdims=True))              # (1, D)
            S = wT[:, i:i + 1] * S + kv
        y_ref[0, rows] = jnp.concatenate(ys, axis=0).astype(y_ref.dtype)
        return S

    S = jax.lax.fori_loop(0, Q // _G, group, s_ref[...])
    s_ref[...] = S

    @pl.when(c == nc - 1)
    def _emit():
        sout_ref[0] = S.astype(sout_ref.dtype)


def wkv6_pallas(r, k, v, w, u, *, s0=None, chunk: int = 128,
                interpret: bool | None = None):
    """r,k,v,w: (B, L, H, D); u: (H, D).  L must be a multiple of the
    chunk, and the chunk of 8 (ops.py pads).  Returns (y,
    final_state)."""
    if interpret is None:
        interpret = default_interpret()
    B, L, H, D = r.shape
    Q = min(chunk, L)
    assert L % Q == 0 and Q % _G == 0, (L, Q)

    def fold(a):
        return jnp.moveaxis(a, 2, 1).reshape(B * H, L, D)

    rf, kf, vf, wf = fold(r), fold(k), fold(v), fold(w)
    s0f = (s0.reshape(B * H, D, D) if s0 is not None
           else jnp.zeros((B * H, D, D), jnp.float32))

    seq_spec = pl.BlockSpec((1, Q, D), lambda bh, c: (bh, c, 0))
    state_spec = pl.BlockSpec((1, D, D), lambda bh, c: (bh, 0, 0))
    y, s_fin = pl.pallas_call(
        functools.partial(_body, Q=Q),
        grid=(B * H, L // Q),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, D, 1), lambda bh, c: (bh % H, 0, 0)),
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B * H, L, D), r.dtype),
                   jax.ShapeDtypeStruct((B * H, D, D), jnp.float32)],
        scratch_shapes=[vmem_scratch((D, D), jnp.float32)],
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "arbitrary")),
    )(rf, kf, vf, wf, u.reshape(H, D, 1), s0f)
    y = jnp.moveaxis(y.reshape(B, H, L, D), 1, 2)
    return y, s_fin.reshape(B, H, D, D)
