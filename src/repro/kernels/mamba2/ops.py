"""Public Mamba2 scan wrapper: dispatch, D-skip fusion, decode step."""
from __future__ import annotations

import jax.numpy as jnp

from ..common import chunk_and_length, resolve_impl
from .kernel import mamba2_scan_pallas
from .ref import mamba2_scan_chunked, mamba2_scan_ref

__all__ = ["mamba2_scan", "mamba2_decode_step"]


def mamba2_scan(x, dt, A, B, C, *, D_skip=None, h0=None,
                return_state: bool = False, impl: str = "auto",
                chunk: int = 256, interpret: bool | None = None):
    """Selective state-space scan.  Shapes as in ref.py."""
    impl = resolve_impl(impl)
    if impl == "sequential":
        return mamba2_scan_ref(x, dt, A, B, C, D_skip=D_skip, h0=h0,
                               return_state=return_state)
    if impl == "reference":
        # block-parallel form: Q-times less state traffic than the
        # sequential scan (EXPERIMENTS.md §Perf H1)
        return mamba2_scan_chunked(x, dt, A, B, C, D_skip=D_skip, h0=h0,
                                   return_state=return_state,
                                   chunk=min(chunk, 256))
    # Pad the sequence to whole chunks with dt = 0 steps, which leave the
    # state untouched and add nothing to earlier outputs.
    L = x.shape[1]
    Q, Lp = chunk_and_length(L, chunk)
    tail = ((0, 0), (0, Lp - L))
    y, h_fin = mamba2_scan_pallas(
        jnp.pad(x, tail + ((0, 0), (0, 0))), jnp.pad(dt, tail + ((0, 0),)),
        A, jnp.pad(B, tail + ((0, 0),)), jnp.pad(C, tail + ((0, 0),)),
        h0=h0, chunk=Q, interpret=interpret)
    y = y[:, :L]
    if D_skip is not None:
        y = y + (D_skip.astype(jnp.float32)[None, None, :, None]
                 * x.astype(jnp.float32)).astype(y.dtype)
    if return_state:
        return y, h_fin
    return y


def mamba2_decode_step(h, x_t, dt_t, A, B_t, C_t, *, D_skip=None):
    """One recurrence step for serving.  h: (Bt, H, N, P); x_t: (Bt, H, P);
    dt_t: (Bt, H); B_t, C_t: (Bt, N).  Returns (y_t, h_new)."""
    hf = h.astype(jnp.float32)
    xf = x_t.astype(jnp.float32)
    dtf = dt_t.astype(jnp.float32)
    decay = jnp.exp(A.astype(jnp.float32)[None, :] * dtf)      # (Bt, H)
    dBx = jnp.einsum("bn,bhp->bhnp", B_t.astype(jnp.float32),
                     xf * dtf[..., None])
    h_new = hf * decay[..., None, None] + dBx
    y = jnp.einsum("bn,bhnp->bhp", C_t.astype(jnp.float32), h_new)
    if D_skip is not None:
        y = y + D_skip.astype(jnp.float32)[None, :, None] * xf
    return y.astype(x_t.dtype), h_new
