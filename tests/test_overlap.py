"""Collective-matmul overlap primitives vs plain matmul (8 host devices
in a subprocess — the main pytest process has 1 device)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.parallel.overlap import all_gather_matmul, matmul_reduce_scatter

mesh = jax.sharding.Mesh(np.asarray(jax.devices()).reshape(8), ("model",))
ks = jax.random.split(jax.random.PRNGKey(0), 2)
M, K, N = 64, 128, 256
x = jax.random.normal(ks[0], (M, K), jnp.float32)
w = jax.random.normal(ks[1], (K, N), jnp.float32) * 0.1
ref = x @ w

# weight-gathered (ICI-Kloop) with overlap
agm = jax.jit(jax.shard_map(
    lambda x, w: all_gather_matmul(x, w, "model"), mesh=mesh,
    in_specs=(P(None, None), P(None, "model")),
    out_specs=P(None, None), axis_names={"model"}, check_vma=False))
out = agm(x, w)
err1 = float(jnp.abs(out - ref).max())

# activation-contracted reduce-scatter (ICI-Mloop) with overlap
mrs = jax.jit(jax.shard_map(
    lambda x, w: matmul_reduce_scatter(x, w, "model"), mesh=mesh,
    in_specs=(P(None, "model"), P("model", None)),
    out_specs=P(None, "model"), axis_names={"model"}, check_vma=False))
out2 = mrs(x, w)
err2 = float(jnp.abs(out2 - ref).max())
print(f"ERRS:{err1:.2e},{err2:.2e}")
assert err1 < 1e-3 and err2 < 1e-3, (err1, err2)
print("OK")
"""


@pytest.mark.slow
def test_collective_matmul_overlap_primitives():
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout, proc.stdout
