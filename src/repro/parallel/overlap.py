"""Compute/communication overlap primitives (T4 on the interconnect).

The paper splits DMA transfers into chunks so loads hide under MAC
latency; the ICI analogue is the *collective matmul*: instead of one
blocking all-gather of the weight shards followed by one big matmul,
the ring is walked one shard at a time — each step's ``ppermute``
transfer overlaps the previous step's partial matmul (XLA schedules the
send/recv pair asynchronously on TPU).  ``core/dataflow.py``'s
``DistDecision.chunks`` picks the chunk count; this module provides the
shard_map-level implementations.

Used inside fully-manual shard_map bodies (see tests/test_overlap.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


__all__ = ["all_gather_matmul", "matmul_reduce_scatter"]


def _ring(axis_name):
    g = jax.lax.axis_size(axis_name)
    return [(i, (i + 1) % g) for i in range(g)]


def all_gather_matmul(x: jax.Array, w_shard: jax.Array,
                      axis_name: str) -> jax.Array:
    """x (M, K) replicated over ``axis_name``; w_shard (K, N/g) local.

    Computes ``x @ W_full`` (M, N) with the weight all-gather unrolled
    around the ring so every transfer overlaps a partial matmul — the
    weight-gathered (ICI-Kloop) execution with T4 chunking applied.
    """
    g = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x.shape[0]
    Nl = w_shard.shape[1]
    buf = jnp.zeros((M, Nl * g), x.dtype)
    w = w_shard
    own = idx
    for _ in range(g):
        part = jnp.dot(x, w, preferred_element_type=jnp.float32)
        buf = jax.lax.dynamic_update_slice(
            buf, part.astype(x.dtype), (0, own * Nl))
        w = jax.lax.ppermute(w, axis_name, _ring(axis_name))
        own = (own - 1) % g
    return buf


def matmul_reduce_scatter(x: jax.Array, w_shard: jax.Array,
                          axis_name: str) -> jax.Array:
    """x (M, K/g local columns... i.e. x_shard (M, Kl)); w_shard (Kl, N).

    Computes the K-contracted ``X_full @ W_full`` reduce-scattered over
    N: returns this rank's (M, N/g) slice.  The ring accumulates partial
    products while they travel — each hop's transfer overlaps the next
    partial matmul (the activation-gathered / ICI-Mloop direction).
    """
    g = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    N = w_shard.shape[1]
    assert N % g == 0
    Nl = N // g
    acc = jnp.zeros((x.shape[0], Nl), jnp.float32)
    for step in range(g):
        # The accumulator visiting rank q at step t ends its journey at
        # rank (q - t - 1) + t+1 hops ... i.e. every visitor adds its
        # partial for the slice the FINAL holder owns: (idx - step - 1).
        target = (idx - step - 1) % g
        w_slice = jax.lax.dynamic_slice(
            w_shard, (0, target * Nl), (w_shard.shape[0], Nl))
        acc = acc + jnp.dot(x, w_slice,
                            preferred_element_type=jnp.float32)
        if step != g - 1:
            acc = jax.lax.ppermute(acc, axis_name, _ring(axis_name))
    return acc.astype(x.dtype)
