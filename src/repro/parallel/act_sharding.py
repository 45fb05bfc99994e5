"""Activation sharding hooks.

Models call ``shard_act(x, "hidden")`` at layer boundaries; a context
(installed by the launcher) maps logical activation names to
PartitionSpecs and applies ``with_sharding_constraint``.  Outside any
context (unit tests, single device) the hook is the identity, so model
code stays mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import contextvars

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["shard_act", "activation_rules", "ActivationRules", "per_shard"]

_CTX: contextvars.ContextVar = contextvars.ContextVar("act_rules",
                                                      default=None)


class ActivationRules:
    """name -> PartitionSpec; unknown names pass through unsharded."""

    def __init__(self, specs: dict[str, P], mesh=None):
        self.specs = specs
        self.mesh = mesh

    def spec(self, x: jax.Array, name: str) -> P:
        """The rule for ``name`` fitted to ``x``: trimmed to its rank
        (specs are written for the canonical rank; reduced ranks drop
        trailing axes), with entries whose dimension the mesh axis does
        not divide dropped.  Unknown names are unsharded."""
        sizes = dict(self.mesh.shape) if self.mesh is not None else {}
        entries = list(self.specs.get(name, ()))[:x.ndim]
        while len(entries) < x.ndim:
            entries.append(None)
        fixed = []
        for dim, e in zip(x.shape, entries):
            names = (e,) if isinstance(e, str) else tuple(e or ())
            total = 1
            for n in names:
                total *= sizes.get(n, 1)
            fixed.append(e if (total and dim % total == 0) else None)
        return P(*fixed)

    def constrain(self, x: jax.Array, name: str) -> jax.Array:
        if name not in self.specs:
            return x
        return jax.lax.with_sharding_constraint(x, self.spec(x, name))


@contextlib.contextmanager
def activation_rules(rules: ActivationRules | None):
    tok = _CTX.set(rules)
    try:
        yield
    finally:
        _CTX.reset(tok)


def shard_act(x: jax.Array, name: str) -> jax.Array:
    rules = _CTX.get()
    if rules is None:
        return x
    return rules.constrain(x, name)


def per_shard(fn, *named):
    """``fn(*xs)`` for named activations ``(x, name)``, run on each
    device's shard when a mesh context is active: the compiler cannot
    partition a Pallas kernel, so a kernel on a mesh is called per shard.

    For (batch, heads, ...) operands such as attention's q/k/v: dims 0
    and 1 keep the rules' sharding of the first operand — heads only
    where every operand shards them alike, so a GQA group never
    straddles devices — and every later dim is whole on each device.
    The output is sharded like the first operand.  Outside a mesh
    context this is a plain call."""
    xs = [x for x, _ in named]
    rules = _CTX.get()
    if rules is None or rules.mesh is None:
        return fn(*xs)
    specs = [rules.spec(x, n) for x, n in named]
    batch = specs[0][0]
    heads = specs[0][1] if len({s[1] for s in specs}) == 1 else None
    in_specs = tuple(P(batch, heads, *([None] * (x.ndim - 2))) for x in xs)
    return jax.shard_map(fn, mesh=rules.mesh, in_specs=in_specs,
                         out_specs=in_specs[0], check_vma=False)(*xs)


def data_shards() -> int:
    """Product of the batch-carrying mesh axes in the active context
    (1 outside any mesh) — the block count for hierarchical dispatch."""
    rules = _CTX.get()
    if rules is None or rules.mesh is None:
        return 1
    sizes = dict(rules.mesh.shape)
    return sizes.get("pod", 1) * sizes.get("data", 1)
