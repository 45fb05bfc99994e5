"""Plain CNN forward: ``jax.lax.conv_general_dilated`` and
``reduce_window`` layer by layer, channels last, FCs on the flattened
(H, W, C) activations -- the layer table of the configuration file read
literally, nothing fused."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .quant import operands


def forward(config: dict, params: dict, x, quant: str | None = None):
    """x: (B, H, W, C) -> logits (B, classes), float32.  Call under
    ``jax.default_matmul_precision("highest")``."""
    h = x.astype(jnp.float32)
    for i, layer in enumerate(config["layers"]):
        kind = layer["kind"]
        if kind == "conv":
            p = params[f"layer_{i:02d}"]
            a, w = operands(quant, h, (1, 2, 3), p["w"], (0, 1, 2))
            pad = layer["pad"]
            h = jax.lax.conv_general_dilated(
                a, w, (layer["stride"],) * 2, ((pad, pad), (pad, pad)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
            h = h + p["b"].astype(jnp.float32)
        elif kind == "maxpool":
            pad = layer["pad"]
            h = jax.lax.reduce_window(
                h, -jnp.inf, jax.lax.max, (1, layer["k"], layer["k"], 1),
                (1, layer["stride"], layer["stride"], 1),
                ((0, 0), (pad, pad), (pad, pad), (0, 0)))
        elif kind == "fc":
            p = params[f"layer_{i:02d}"]
            a, w = operands(quant, h.reshape(h.shape[0], -1), (1,),
                            p["w"], (0,))
            h = a @ w + p["b"].astype(jnp.float32)
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
        if layer.get("activation") == "relu":
            h = jax.nn.relu(h)
        if quant == "bfloat16":
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
    return h
