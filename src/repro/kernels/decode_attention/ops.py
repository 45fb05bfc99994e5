"""Public decode-attention wrapper with pallas/reference dispatch.

The cache operand is a **ring buffer**: callers that decode past the
cache length (a rolling full-length cache, or a sliding-window cache
sized ``W = min(max_len, attn_window)``) write the new token's K/V at
``pos % S`` and pass ``kv_len = ring_kv_len(pos, S)`` — the last
``min(pos + 1, S)`` rows are then valid and everything at ring slots
``>= kv_len`` (unwritten padding, or rows evicted by overwrite) is
masked out.  Row *order* inside the ring does not matter: RoPE bakes
each row's absolute position into its key, and softmax attention is
permutation-invariant over KV rows, so the wrapped layout attends
identically to the chronological one (the legacy
``transformer._attention_decode`` rule this kernel inherits).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common import resolve_impl
from ...core.hw import TPU_V5E, HardwareModel
from .kernel import decode_attention_pallas, paged_decode_attention_pallas
from .ref import decode_attention_ref

__all__ = ["decode_attention", "paged_decode_attention", "gather_pages",
           "ring_kv_len", "ring_positions", "fold_kv", "unfold_kv"]


def fold_kv(x, fold: int):
    """(..., S, D) -> (..., S / fold, fold * D): row ``fold * j + p``
    moves to packed row ``j``, lanes ``[p * D, (p + 1) * D)`` — the
    lane-dense layout of a persistent KV region
    (``core/regions.py::kv_cache_spec``).  A row-major reshape, so it
    costs nothing on head-major (..., S, D) data."""
    *lead, S, D = x.shape
    return x.reshape(*lead, S // fold, fold * D)


def unfold_kv(x, fold: int):
    """Inverse of ``fold_kv``: (..., S / fold, fold * D) -> (..., S, D)."""
    *lead, Sf, W = x.shape
    return x.reshape(*lead, Sf * fold, W // fold)


def ring_positions(length, cache_len: int, seq_len: int):
    """Source position for every ring slot of a rolling cache holding
    the last ``min(length, cache_len)`` of ``seq_len`` computed rows:
    slot ``j`` holds the latest position ``p < length`` with ``p %
    cache_len == j``.  Returns (cache_len,) int32 gather indices into
    the full (seq_len, ...) row stack; ``length`` may be a traced
    scalar (the runtime prompt length).

    Slots with no valid position (j >= length) fall out of range and
    are clipped — they end up *duplicating* an early row, not holding
    zeros.  That is safe because such slots sit at ring indices ``>=
    ring_kv_len(length - 1, cache_len)`` and decode overwrites slot
    ``pos % cache_len`` at the exact tick ``ring_kv_len`` first admits
    it, so a duplicate is never attended.

    This is THE ring-layout rule: the prefill executor
    (runtime/executor.py::_write_prefill_cache) gathers with it at a
    runtime length, and the legacy cache export (models/transformer.py
    ::forward ``return_cache``) uses it at ``length == seq_len`` — one
    shared rule, like ``ring_kv_len``, so the two layouts can never
    drift."""
    j = jnp.arange(cache_len)
    last = jnp.asarray(length, jnp.int32) - 1
    p = j + ((last - j) // cache_len) * cache_len
    return jnp.clip(p, 0, seq_len - 1)


def ring_kv_len(pos, cache_len: int):
    """Valid-row count of a rolling (ring) KV cache after the write at
    ``pos % cache_len`` has landed: the last ``min(pos + 1, cache_len)``
    tokens are attendable, older rows have been evicted by overwrite.
    One rule shared by the legacy decode loop
    (models/transformer.py::_attention_decode) and the decode-Program
    executor (runtime/executor.py::run_decode) so the two paths can
    never drift."""
    return jnp.minimum(pos + 1, cache_len)


def decode_attention(q, k, v, *, kv_len=None, scale: float | None = None,
                     impl: str = "auto", block_kv: int | None = None,
                     fold: int = 1, hw: HardwareModel = TPU_V5E,
                     interpret: bool | None = None) -> jax.Array:
    """Single-token decode: q (B,Hq,D) vs cache (B,Hkv,S/fold,fold*D),
    the lane-dense layout ``fold_kv`` makes (fold 1: (B,Hkv,S,D))."""
    B, Hq, D = q.shape
    S = k.shape[2] * fold
    scale = scale if scale is not None else D ** -0.5
    if kv_len is None:
        kv_len = jnp.full((B,), S, jnp.int32)
    impl = resolve_impl(impl)
    if impl == "reference":
        return decode_attention_ref(q, unfold_kv(k, fold),
                                    unfold_kv(v, fold), kv_len=kv_len,
                                    scale=scale)
    if block_kv is None:
        # T2 decode regime: cache block sized to stream at full
        # bandwidth, k+v double buffered.  One chooser shared with the
        # compiler (core/tiling.py) — the decode-Program lowering pins
        # the same value into each decode_attention op, so this branch
        # only runs for direct (non-Program) kernel calls.  A windowed
        # cache is already window-sized, so S is the right extent.
        from ...core.tiling import select_attention_blocks
        _, block_kv = select_attention_blocks(1, S, D, k.dtype.itemsize, hw)
    pad = ((-S) % block_kv) // fold
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return decode_attention_pallas(q, k, v, kv_len, scale=scale,
                                   block_kv=block_kv, fold=fold,
                                   interpret=interpret)


def gather_pages(pages, table, scale=None):
    """Materialize the contiguous (B, Hkv, S, D) cache view of a page
    pool through a page table — THE table-indirection rule, shared by
    the reference paged-attention path below and any caller that needs
    the flat layout (tests, the engine's debug dumps).

    pages: (n_pages, page_size, Hkv, D) pool (any dtype; int8 pools are
    dequantized when ``scale`` — per-page (n_pages,) float32 — is
    given); table: (B, pages_per_slot) int32.  Row ``s`` of slot ``b``
    is pool row ``(table[b, s // page_size], s % page_size)``; the null
    page 0 supplies whatever masked writes left there, which is fine
    because every row it backs sits beyond the caller's ``kv_len`` or
    below its shared-prefix redirect."""
    gathered = pages[table]          # (B, pages_per_slot, page_size, Hkv, D)
    if scale is not None:
        gathered = gathered.astype(jnp.float32) * scale[table][
            :, :, None, None, None]
    B, P, G, Hkv, D = gathered.shape
    return gathered.reshape(B, P * G, Hkv, D).transpose(0, 2, 1, 3)


def paged_decode_attention(q, k_pages, v_pages, page_table, *, kv_len,
                           scale: float | None = None,
                           k_scale=None, v_scale=None,
                           impl: str = "auto",
                           interpret: bool | None = None) -> jax.Array:
    """Single-token decode against a **paged** KV cache (§5.1 paged
    region plan): q (B, Hq, D) vs pools (n_pages, page_size, Hkv, D)
    addressed through ``page_table`` (B, pages_per_slot) int32.

    The virtual row range of slot ``b`` is its table row flattened —
    ``cache_len = pages_per_slot * page_size`` — and the same ring
    rules apply *through the table*: callers pass ``kv_len =
    ring_kv_len(pos, cache_len)`` and write the new token's K/V at
    virtual row ``pos % cache_len`` (i.e. into page ``row //
    page_size``), so rolling overwrite past ``cache_len`` works
    unchanged.  int8 pools carry one float32 scale per page
    (``k_scale`` / ``v_scale``), applied in the gather.

    There is no block_kv knob: the kv block IS the page
    (core/tiling.py pins block_kv == page_size for paged decode ops)."""
    B, Hq, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    if kv_len is None:
        kv_len = jnp.full((B,), page_table.shape[1] * k_pages.shape[1],
                          jnp.int32)
    impl = resolve_impl(impl)
    if impl == "reference":
        k = gather_pages(k_pages, page_table, k_scale)
        v = gather_pages(v_pages, page_table, v_scale)
        return decode_attention_ref(q, k, v, kv_len=kv_len, scale=scale)
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, kv_len, scale=scale,
        k_scale=k_scale, v_scale=v_scale, interpret=interpret)
