"""Model substrate: parameter definition trees, init, abstract params,
logical-axis sharding specs, norms, rotary embeddings.

Parameters are declared as ``ParamDef`` trees (shape + dtype + logical
axes + init kind).  From one declaration we derive:
  * concrete initialization (``init_params``),
  * allocation-free abstract params for the dry-run (``abstract_params``),
  * ``PartitionSpec`` trees from logical->mesh axis rules
    (``param_pspecs``) — the distributed half of the schedule compiler
    plugs in here (parallel/rules.py chooses the rules per layer class).

Repeated transformer blocks are *stacked* on a leading "layers" axis and
executed with ``jax.lax.scan`` so the HLO stays one-block-sized — which
keeps the 512-device dry-run compile tractable.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = [
    "ParamDef", "init_params", "abstract_params", "param_pspecs",
    "tree_paths", "rms_norm", "layer_norm", "Rotary", "apply_rope",
    "cross_entropy_loss", "count_params",
]


@dataclass(frozen=True)
class ParamDef:
    shape: tuple[int, ...]
    axes: tuple[Any, ...]                 # logical axis names (or None)
    dtype: Any = jnp.bfloat16
    init: str = "normal"                  # normal | zeros | ones | embed
    init_scale: float | None = None       # overrides fan-in scaling

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _fan_in(shape: tuple[int, ...]) -> int:
    if len(shape) == 1:
        return shape[0]
    return int(jnp.prod(jnp.array(shape[:-1])).item()) if False else \
        math.prod(shape[:-1])


def _init_leaf(rng, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "embed":
        scale = d.init_scale if d.init_scale is not None else 0.02
        return (jax.random.normal(rng, d.shape, jnp.float32)
                * scale).astype(d.dtype)
    # fan-in scaled normal; stacked layer axes excluded from fan-in.
    shape = d.shape
    fan_shape = shape[1:] if (d.axes and d.axes[0] == "layers") else shape
    fan = _fan_in(fan_shape) if len(fan_shape) > 1 else fan_shape[0]
    scale = d.init_scale if d.init_scale is not None else fan ** -0.5
    return (jax.random.normal(rng, d.shape, jnp.float32)
            * scale).astype(d.dtype)


def tree_paths(defs: dict, prefix: str = "") -> list[str]:
    out = []
    for k, v in defs.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.extend(tree_paths(v, p))
        else:
            out.append(p)
    return out


def init_params(defs: dict, rng: jax.Array) -> dict:
    """Initialize a ParamDef tree to concrete arrays (deterministic per
    path, so restores and re-inits agree regardless of traversal order).
    The path is folded in by CRC-32, not ``hash()``: str hashes are
    salted per process, so the same seed would give other weights in
    every run."""
    paths = tree_paths(defs)
    keys = {p: jax.random.fold_in(rng, zlib.crc32(p.encode()) % (2 ** 31))
            for p in paths}

    def go(sub: dict, prefix: str) -> dict:
        out = {}
        for k, v in sub.items():
            p = f"{prefix}/{k}" if prefix else k
            out[k] = go(v, p) if isinstance(v, dict) else _init_leaf(keys[p], v)
        return out

    return go(defs, "")


def abstract_params(defs: dict) -> dict:
    """ShapeDtypeStruct tree — the dry-run's allocation-free params."""
    def go(sub):
        return {k: go(v) if isinstance(v, dict)
                else jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in sub.items()}
    return go(defs)


def param_pspecs(defs: dict, rules: dict,
                 overrides: dict | None = None,
                 axis_sizes: dict | None = None) -> dict:
    """Map logical axes -> mesh axes (rules values: None, str, or tuple).

    A mesh axis may appear only once per tensor; when two logical axes
    map to the same mesh axis, the earlier tensor axis wins (e.g. MoE
    weights (experts, embed, ff) with experts->model keep ff unsharded).
    Entries whose dimension is not divisible by the mesh-axis size are
    dropped (jit in/out shardings require even sharding).
    ``overrides``: path-suffix -> rules dict, for per-layer-class
    strategies chosen by the distributed Mloop/Kloop cost model.
    """
    def spec(d: ParamDef, ruleset: dict) -> P:
        entries = []
        used: set[str] = set()
        for ax, dim in zip(d.axes, d.shape):
            r = ruleset.get(ax) if ax is not None else None
            names = (r,) if isinstance(r, str) else tuple(r or ())
            if axis_sizes is not None and names:
                total = 1
                for n in names:
                    total *= axis_sizes.get(n, 1)
                if total and dim % total != 0:
                    r, names = None, ()
            if any(n in used for n in names):
                r = None
            else:
                used.update(names)
            entries.append(r)
        return P(*entries)

    def pick_rules(path: str) -> dict:
        if overrides:
            best = None
            for suffix, rs in overrides.items():
                if path.endswith(suffix):
                    if best is None or len(suffix) > len(best[0]):
                        best = (suffix, rs)
            if best is not None:
                return best[1]
        return rules

    def go(sub, prefix=""):
        out = {}
        for k, v in sub.items():
            p = f"{prefix}/{k}" if prefix else k
            out[k] = (go(v, p) if isinstance(v, dict)
                      else spec(v, pick_rules(p)))
        return out
    return go(defs)


def count_params(defs: dict) -> int:
    def go(sub):
        t = 0
        for v in sub.values():
            t += go(v) if isinstance(v, dict) else math.prod(v.shape)
        return t
    return go(defs)


# --- norms --------------------------------------------------------------------
def rms_norm(x: jax.Array, weight: jax.Array | None, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(x.dtype)


def layer_norm(x: jax.Array, weight=None, bias=None, eps: float = 1e-5):
    """LayerNorm; with weight=bias=None this is OLMo's non-parametric LN."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# --- rotary -------------------------------------------------------------------
@dataclass(frozen=True)
class Rotary:
    head_dim: int
    theta: float = 10000.0

    def freqs(self, positions: jax.Array) -> tuple[jax.Array, jax.Array]:
        """positions: (...,) int -> (cos, sin) of shape (..., head_dim/2)."""
        half = self.head_dim // 2
        inv = self.theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
        ang = positions.astype(jnp.float32)[..., None] * inv
        return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, D); cos/sin: (S, D/2) or broadcastable."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    while cos.ndim < x1.ndim:
        cos, sin = cos[None], sin[None]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


# --- loss ---------------------------------------------------------------------
def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE.  logits (B, L, V) f32-upcast; labels (B, L)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is not None:
        nll = nll * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    return nll.mean()
