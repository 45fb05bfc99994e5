"""Lower precisions for the control: every matmul or conv operand
rounded as a kernel in that precision would see it."""
from __future__ import annotations

import jax.numpy as jnp


def int8(x, axes):
    """Symmetric int8 with one scale per slice, the max taken over
    ``axes`` (the reduction axes), dequantized back to float32."""
    x = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def operands(quant: str | None, x, x_axes, w, w_axes):
    """(x, w) as the control's matmul sees them.  ``None``: float32;
    "bfloat16": rounded to bfloat16; "int8": int8 activations per row
    and int8 weights per output channel."""
    if quant is None:
        return x.astype(jnp.float32), w.astype(jnp.float32)
    if quant == "bfloat16":
        return (x.astype(jnp.bfloat16).astype(jnp.float32),
                w.astype(jnp.bfloat16).astype(jnp.float32))
    if quant == "int8":
        return int8(x, x_axes), int8(w, w_axes)
    raise ValueError(f"unknown control precision {quant!r}")
