"""Public WKV6 wrapper + decode step."""
from __future__ import annotations

import jax.numpy as jnp

from ..common import chunk_and_length, resolve_impl
from .kernel import wkv6_pallas
from .ref import wkv6_chunked, wkv6_ref

__all__ = ["wkv6", "wkv6_decode_step"]


def wkv6(r, k, v, w, u, *, s0=None, return_state: bool = False,
         impl: str = "auto", chunk: int = 128,
         interpret: bool | None = None):
    impl = resolve_impl(impl)
    if impl == "sequential":
        return wkv6_ref(r, k, v, w, u, s0=s0, return_state=return_state)
    if impl == "reference":
        # block-parallel form (see §Perf H1); sequential oracle retained
        return wkv6_chunked(r, k, v, w, u, s0=s0,
                            return_state=return_state)
    # Pad the sequence to whole chunks with k = 0, w = 1 steps, which
    # leave the state untouched.
    L = r.shape[1]
    Q, Lp = chunk_and_length(L, chunk)
    tail = ((0, 0), (0, Lp - L), (0, 0), (0, 0))
    y, s_fin = wkv6_pallas(jnp.pad(r, tail), jnp.pad(k, tail),
                           jnp.pad(v, tail),
                           jnp.pad(w, tail, constant_values=1), u, s0=s0,
                           chunk=Q, interpret=interpret)
    y = y[:, :L]
    if return_state:
        return y, s_fin
    return y


def wkv6_decode_step(S, r_t, k_t, v_t, w_t, u):
    """One step for serving.  S: (B, H, D, D); r/k/v/w_t: (B, H, D);
    u: (H, D).  Returns (y_t, S_new)."""
    Sf = S.astype(jnp.float32)
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r_t, k_t, v_t, w_t))
    kv = kf[..., :, None] * vf[..., None, :]
    y = jnp.einsum("bhi,bhij->bhj",
                   rf, Sf + u.astype(jnp.float32)[None, :, :, None] * kv)
    S_new = wf[..., :, None] * Sf + kv
    return y.astype(r_t.dtype), S_new
