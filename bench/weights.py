"""Weights made by the benchmark from ``--seed``, on the device, in one
jitted call and in the type they are served in.

The tree has the names and shapes the program's engine reads, laid out
by the configuration's kind (``bench/kinds/<kind>.py::weight_shapes``)
from the benchmark's own configuration file, so both the program and
the plain reference are handed the same arrays and neither makes them.
A kind that defines ``init_leaf(path, key, shape, dtype, stacked)``
makes the leaves for which it returns an array (a leaf that must keep a
sign or a range, such as a state-space model's ``A_log``).  Every other
matrix is a fan-in scaled normal, the embedding a normal of standard
deviation 0.02, norm weights ones and biases zeros.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int) -> jax.Array:
    state = np.random.SeedSequence([seed, 2]).generate_state(1)[0]
    return jax.random.PRNGKey(int(state))


def _leaf(key, path: str, shape: tuple, dtype, stacked: bool):
    name = path.rsplit("/", 1)[-1]
    if name in ("b",):
        return jnp.zeros(shape, dtype)
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name == "embed":
        scale = 0.02
    else:
        fan = int(np.prod(shape[1 if stacked else 0:-1]))
        scale = fan ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _make(shapes: dict, dtype, stacked_under: str | None, key,
          init_leaf=None):
    leaves = []

    def walk(sub, prefix):
        for k in sorted(sub):
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(sub[k], dict):
                walk(sub[k], p)
            else:
                leaves.append((p, sub[k]))
    walk(shapes, "")
    keys = jax.random.split(key, len(leaves))
    out: dict = {}
    for kk, (path, shape) in zip(keys, leaves):
        stacked = stacked_under is not None and path.startswith(stacked_under)
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        leaf = (None if init_leaf is None
                else init_leaf(path, kk, shape, dtype, stacked))
        node[name] = (_leaf(kk, path, shape, dtype, stacked)
                      if leaf is None else leaf)
    return out


def make(config: dict, seed: int) -> dict:
    from bench import spec
    kind = spec.kind(config)
    shapes, dtype, stacked = kind.weight_shapes(config)
    dtype = jnp.dtype(dtype)
    init_leaf = getattr(kind, "init_leaf", None)
    fn = jax.jit(lambda k: _make(shapes, dtype, stacked, k, init_leaf))
    return jax.block_until_ready(fn(key_for(seed)))
