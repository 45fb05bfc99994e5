"""Shared kernel utilities: padding, epilogue math, compiler params."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ..core.hw import TPU_V5E

__all__ = ["pltpu", "compiler_params", "pad_to", "unpad", "apply_activation",
           "ACTIVATIONS", "vmem_scratch", "default_interpret",
           "chunk_and_length", "resolve_impl"]


def default_interpret() -> bool:
    """Pallas runs in interpret mode off-TPU (the CPU test path)."""
    return jax.default_backend() != "tpu"


def resolve_impl(impl: str, tpu: str = "pallas") -> str:
    """``impl="auto"``: the Pallas kernel (``tpu``) on a TPU, the
    pure-jnp reference elsewhere — the CPU test path.  Any explicit
    impl passes through unchanged."""
    if impl != "auto":
        return impl
    return tpu if jax.default_backend() == "tpu" else "reference"


def compiler_params(dimension_semantics: tuple[str, ...]
                    ) -> pltpu.CompilerParams:
    """Mosaic compiler params (ignored in interpret mode).

    The scoped-VMEM limit is the v5e model's VMEM capacity: the tilers
    (core/tiling.py) size every working set to ``vmem_budget()``, a
    fraction of that capacity, so the limit Mosaic enforces covers the
    tile the schedule chose plus the compiler's own internal scratch.
    Mosaic's default (16 MiB) would refuse any tile larger than that."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=TPU_V5E.vmem_bytes)


def chunk_and_length(L: int, chunk: int) -> tuple[int, int]:
    """(Q, Lp) for a chunked scan: the chunk is ``chunk``, or the whole
    sequence rounded up to the 8-row sublane tile when shorter, and the
    sequence is padded to Lp, a multiple of Q."""
    Q = min(chunk, -(-L // 8) * 8)
    return Q, -(-L // Q) * Q


def pad_to(x: jax.Array, multiples: tuple[int, ...]) -> jax.Array:
    """Zero-pad trailing dims of ``x`` up to the given multiples."""
    pads = []
    for dim, m in zip(x.shape[-len(multiples):], multiples):
        target = ((dim + m - 1) // m) * m
        pads.append((0, target - dim))
    full = [(0, 0)] * (x.ndim - len(multiples)) + pads
    if all(p == (0, 0) for p in full):
        return x
    return jnp.pad(x, full)


def unpad(x: jax.Array, shape: tuple[int, ...]) -> jax.Array:
    if tuple(x.shape) == tuple(shape):
        return x
    return x[tuple(slice(0, s) for s in shape)]


def _silu(x):
    return x * jax.nn.sigmoid(x)


ACTIVATIONS = {
    None: lambda x: x,
    "none": lambda x: x,
    "relu": lambda x: jnp.maximum(x, 0),
    "silu": _silu,
    "swish": _silu,
    "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "tanh": jnp.tanh,
}


def apply_activation(x: jax.Array, name: str | None) -> jax.Array:
    return ACTIVATIONS[name](x)


def vmem_scratch(shape, dtype):
    """VMEM scratch shape (works in interpret mode too)."""
    return pltpu.VMEM(shape, dtype)
