"""Capacity-bounded top-k MoE dispatch with shard-local routing.

T4 made first-class: expert load is a load-balancing problem with the
paper's percent-imbalance metric; capacity bounds L_max exactly like the
paper's DMA chunking bounds the slowest load unit.

Distribution (§Perf H3): the data-dependent dispatch (scatter into the
(E, cap, D) buffer, gather back) runs under ``jax.shard_map`` *manual*
over the batch axes — each data shard routes only its own tokens, so
the scatter/gather are provably chip-local.  The expert matmuls keep
the "model" axis *auto*: D/F stay GSPMD-sharded inside the body
("moe_buf"/"moe_h" rules), and the only cross-shard traffic is the
(E, D, F) weight-gradient reduction inserted by shard_map's transpose —
the same all-reduce any dense layer pays.

Two earlier versions are logged in EXPERIMENTS.md §Perf H3: global
dispatch (GSPMD last-resort replication: 42.9 GB scatters) and
hierarchical-indices-under-jit (the partitioner cannot prove block
locality of dynamic indices: worse).

Returns the per-step imbalance statistic so the training loop can log
C_L and apply the auxiliary balancing loss.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.balance import moe_capacity
from ..parallel.act_sharding import _CTX, shard_act
from ..kernels.common import apply_activation

__all__ = ["moe_mlp"]


def _moe_local(x, router_w, w_gate, w_up, w_down, *, top_k, cap_frac,
               activation, gated, valid_count=None, axes=(),
               model_axis=None):
    """Dispatch + expert FFN on the local token block.

    Fully manual under shard_map: w_gate/w_up arrive F-sharded and
    w_down F-sharded over ``model_axis``; the expert FFN computes its
    local F slice with one psum on the output partials."""
    T, D = x.shape
    E = router_w.shape[-1]
    # with_sharding_constraint is illegal inside a fully-manual body
    cons = shard_act if (model_axis is None and not axes) else \
        (lambda a, n: a)
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    cap = moe_capacity(T, E, top_k, cap_frac).capacity_per_expert
    cap = min(cap, T)

    flat_e = top_e.reshape(-1)                              # (T*k,)
    eff_cap = cap
    if valid_count is not None:
        # Right-padded token block (Program prefill pins (1, max_len)):
        # pad rows route to a sentinel expert E so they never claim
        # capacity, and the effective bound is re-derived at the *true*
        # token count — the same `moe_capacity` arithmetic, traced — so
        # per-expert bucketing is identical to the un-padded legacy
        # call and parity holds bit-for-bit on the kept rows.
        mean = valid_count.astype(jnp.float32) * top_k / E
        dyn = jnp.maximum(jnp.ceil(mean * cap_frac / 8.0) * 8.0, 8.0)
        eff_cap = jnp.minimum(dyn.astype(jnp.int32),
                              valid_count.astype(jnp.int32))
        tok_valid = jnp.arange(T) < valid_count
        flat_e = jnp.where(jnp.repeat(tok_valid, top_k), flat_e, E)
    order = jnp.argsort(flat_e, stable=True)
    counts_full = jnp.bincount(flat_e, length=E + 1)
    offsets = jnp.cumsum(counts_full) - counts_full
    ranks_sorted = jnp.arange(T * top_k) - offsets[flat_e[order]]
    ranks = jnp.zeros(T * top_k, jnp.int32).at[order].set(
        ranks_sorted.astype(jnp.int32))
    counts = counts_full[:E]
    keep = (ranks < eff_cap) & (flat_e < E)
    slot = jnp.where(keep, flat_e * cap + ranks, E * cap)

    x_rep = jnp.repeat(x, top_k, axis=0)                    # static pattern
    buf = jnp.zeros((E * cap + 1, D), x.dtype).at[slot].set(x_rep)
    ebuf = cons(buf[:E * cap].reshape(E, cap, D), "moe_buf")

    h = jnp.einsum("ecd,edf->ecf", ebuf, w_gate,
                   preferred_element_type=jnp.float32)
    h = cons(h, "moe_h")
    h = apply_activation(h, activation)
    if gated:
        up = cons(jnp.einsum("ecd,edf->ecf", ebuf, w_up,
                             preferred_element_type=jnp.float32), "moe_h")
        h = h * up
    out_e = jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), w_down,
                       preferred_element_type=jnp.float32)
    if model_axis is not None:
        # reduce-scatter the F-contraction partials onto D slices: half
        # the ring bytes of a psum, and the combine gather below then
        # reads a 1/model-sized buffer; the (Tl, D/model) result is
        # all-gathered at the end (§Perf H3 iter 4).
        out_e = jax.lax.psum_scatter(out_e, model_axis,
                                     scatter_dimension=2, tiled=True)
    out_e = cons(out_e, "moe_buf")

    Dl = out_e.shape[-1]
    flat_out = jnp.concatenate(
        [out_e.reshape(E * cap, Dl).astype(jnp.float32),
         jnp.zeros((1, Dl), jnp.float32)], axis=0)
    gathered = flat_out[slot] * top_p.reshape(-1)[:, None]
    out = gathered.reshape(T, top_k, Dl).sum(axis=1).astype(x.dtype)
    if model_axis is not None:
        out = jax.lax.all_gather(out, model_axis, axis=1, tiled=True)

    # T4 stats, reduced across the manual axes when present.
    g_counts = counts.astype(jnp.float32)
    frac_probs = probs.mean(axis=0)
    mean_load = jnp.maximum(g_counts.mean(), 1e-9)
    imbalance = (g_counts.max() / mean_load - 1.0) * 100.0
    frac_tokens = g_counts / jnp.maximum(g_counts.sum(), 1.0)
    lb_loss = E * jnp.sum(frac_tokens * frac_probs)
    dropped = 1.0 - keep.mean()
    aux = {"lb_loss": lb_loss, "imbalance_pct": imbalance,
           "dropped_frac": dropped}
    if axes:   # 1-element leaves so shard_map out_specs can carry them
        aux = {k: v[None] for k, v in aux.items()}
    return out, aux


def moe_mlp(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
            w_up: jax.Array, w_down: jax.Array, *, top_k: int,
            capacity_factor: float = 1.25, activation: str = "silu",
            gated: bool = True, valid_count=None):
    """x: (T, D); router_w: (D, E); w_gate/w_up: (E, D, F); w_down: (E, F, D).

    ``valid_count`` (traced scalar) marks x as right-padded: only the
    first ``valid_count`` rows are real tokens; pad rows neither claim
    expert capacity nor perturb the bucketing of real ones.

    Returns (out (T, D), aux).
    """
    rules = _CTX.get()
    mesh = rules.mesh if rules is not None else None
    fn = functools.partial(_moe_local, top_k=top_k,
                           cap_frac=capacity_factor,
                           activation=activation, gated=gated,
                           valid_count=valid_count)
    if mesh is None or valid_count is not None:
        return fn(x, router_w, w_gate, w_up, w_down)

    sizes = dict(mesh.shape)
    dp = tuple(a for a in ("pod", "data") if sizes.get(a, 1) > 1)
    S = 1
    for a in dp:
        S *= sizes[a]
    T = x.shape[0]
    F = w_gate.shape[-1]
    mdl = "model" if sizes.get("model", 1) > 1 and F % sizes["model"] == 0 \
        else None
    # The manual path pays a per-call weight transfer; for decode-sized
    # token counts (weights >> activations) the plain GSPMD path is
    # strictly cheaper (llama4 decode regressed 2.4x under shard_map —
    # §Perf H3 note).
    if not dp or T % S != 0 or T // S < max(top_k, 256):
        return fn(x, router_w, w_gate, w_up, w_down)

    # Fully manual shard_map (every mesh axis listed): the partial-auto
    # mode miscompiles on the CPU backend (all-reduce with a copy
    # combiner), and full-manual is explicit about the single psum the
    # expert FFN needs.
    axes = set(dp) | ({mdl} if mdl else set())
    body = functools.partial(fn, axes=dp, model_axis=mdl)
    f_spec = P(None, None, mdl)        # w_gate / w_up: F-sharded
    d_spec = P(None, mdl, None)        # w_down: F-sharded on dim 1
    wrapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), f_spec, f_spec, d_spec),
        out_specs=(P(dp, None),
                   {"lb_loss": P(dp), "imbalance_pct": P(dp),
                    "dropped_frac": P(dp)}),
        axis_names=axes, check_vma=False)
    out, aux = wrapped(x, router_w, w_gate, w_up, w_down)
    aux = {k: jnp.mean(v) for k, v in aux.items()}
    return out, aux
