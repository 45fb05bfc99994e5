"""bench/weights.py: a kind may seed its own leaves; the kinds that do
not keep the weights they had for a seed, bit for bit."""
import hashlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights

SEED = 2 ** 32 + 3
# sha256 over every leaf's path and bytes, in path order, of the
# weights made for SEED before a kind could seed its own leaves.
DIGESTS = {
    "bench/tests/data/configs/smollm-360m-smoke.json":
        "0ada1da8f29cef97656cf232a6d1d97f8422815187639661946e21f898f03626",
    "bench/configs/alexnet-owt.json":
        "ab621f84ecd4836b6987e8c694e4923f6d40f68ef5a96877e573e51e9e1a6d9c",
}


def digest(params) -> str:
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("path", sorted(DIGESTS))
def test_kinds_without_init_leaf_keep_their_weights(path):
    config = spec.load_json(spec.REPO / path)
    assert not hasattr(spec.kind(config), "init_leaf")
    assert digest(weights.make(config, SEED)) == DIGESTS[path]


def test_a_kind_seeds_its_own_leaves(monkeypatch):
    """``init_leaf`` makes the leaves it returns an array for, from the
    leaf's own key; the others follow the common rule, with the keys
    they would have had."""
    seen = []

    def init_leaf(path, key, shape, dtype, stacked):
        seen.append((path, shape, stacked))
        if path.endswith("A_log"):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0)).astype(dtype)
        return None
    shapes = {"blocks": {"A_log": (2, 4), "w": (2, 8, 4)}, "embed": (16, 8)}
    kind = SimpleNamespace(weight_shapes=lambda c: (shapes, "float32",
                                                    "blocks/"),
                           init_leaf=init_leaf)
    plain = SimpleNamespace(weight_shapes=kind.weight_shapes)
    monkeypatch.setattr(spec, "kind", lambda c: kind)
    p = weights.make({}, 7)
    monkeypatch.setattr(spec, "kind", lambda c: plain)
    q = weights.make({}, 7)
    assert sorted(seen) == [("blocks/A_log", (2, 4), True),
                            ("blocks/w", (2, 8, 4), True),
                            ("embed", (16, 8), False)]
    a = np.exp(np.asarray(p["blocks"]["A_log"]))
    assert ((a >= 1.0) & (a <= 16.0)).all()
    assert not np.array_equal(p["blocks"]["A_log"], q["blocks"]["A_log"])
    for leaf in (("blocks", "w"), ("embed",)):
        x, y = p, q
        for k in leaf:
            x, y = x[k], y[k]
        assert np.array_equal(x, y)
