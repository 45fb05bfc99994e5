"""Main-path Pallas kernels compiled by Mosaic for a described TPU v5e.

Interpret mode accepts layouts the chip's compiler refuses (sub-tile
blocks, strided vector slices, more VMEM than a kernel may claim).
These tests compile each kernel at the widths of a model the repo
serves, with ``impl="pallas", interpret=False``, against a ``v5e:2x2``
topology described without a chip: nothing runs, so only the compile
is checked.  Numerics are checked by the interpret-mode parity tests
and, on a chip, by ``chip_smoke.py``.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and each
test worker imports every test file.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (conv2d, decode_attention, flash_attention,
                           mamba2_scan, matmul, paged_decode_attention,
                           wkv6)

BF16 = jnp.bfloat16
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


KW = dict(impl="pallas", interpret=False)


@pytest.mark.parametrize("M,K,N", [
    (256, 960, 960),        # smollm-360m q/o projection, 256-token chunk
    (256, 960, 2560),       # smollm-360m FFN up
    (2048, 960, 49152),     # smollm-360m prefill lm_head
    (4096, 4096, 14336),    # llama3-8b-width FFN
])
def test_matmul_compiles(one_chip, M, K, N):
    _compile(lambda a, b: matmul(a, b, **KW), one_chip,
             ((M, K), BF16), ((K, N), BF16))


def test_flash_attention_fwd_compiles(one_chip):
    # smollm-360m causal prefill, S=2048, GQA 15/5, head_dim 64.
    _compile(lambda q, k, v: flash_attention(q, k, v, causal=True, **KW),
             one_chip, ((1, 15, 2048, 64), BF16), ((1, 5, 2048, 64), BF16),
             ((1, 5, 2048, 64), BF16))


def test_flash_attention_bwd_compiles(one_chip):
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, impl="pallas_trainable",
                            interpret=False)
        return o.astype(F32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
             ((2, 15, 2048, 64), BF16), ((2, 5, 2048, 64), BF16),
             ((2, 5, 2048, 64), BF16))


@pytest.mark.parametrize("fold", [1, 2])
def test_decode_attention_compiles(one_chip, fold):
    # 8 slots x 2048 cache rows at smollm-360m widths: head-major
    # (8, 5, 2048, 64), and lane-dense with two rows a 128-lane row.
    kv = ((8, 5, 2048 // fold, 64 * fold), BF16)
    _compile(lambda q, k, v, n: decode_attention(q, k, v, kv_len=n,
                                                 fold=fold, **KW),
             one_chip, ((8, 15, 64), BF16), kv, kv, ((8,), jnp.int32))


# An HLO instruction that copies or transposes: its result type and op.
_MOVE = re.compile(r"= \(?(\w+)\[([\d,]*)\]\S* (copy|copy-start|transpose)\(")


@pytest.mark.parametrize("lane_dense", [True, False])
def test_decode_layer_moves_no_kv_region(one_chip, lane_dense):
    """One smollm-360m decode layer (64 slots x 2048 rows, 5 KV heads of
    64, the regions donated) through ``_run_decode_attention``: with the
    lane-dense regions ``kv_cache_spec`` gives, the compiled step copies
    and transposes nothing of a region's size — the row is written in
    place and the kernel reads the region as it rests.  The row-major
    layout, as a control, is copied whole."""
    from repro.core import regions
    from repro.core.program import AttentionSpec, ProgramOp
    from repro.runtime.executor import _run_decode_attention
    slots, rows, H, KV, hd = 64, 2048, 15, 5, 64
    spec = regions.kv_cache_spec("l0.k_cache", slots, rows, KV, hd, BF16)
    fold = spec.fold if lane_dense else None
    shape = spec.shape if lane_dense else (slots, rows, KV, hd)
    op = ProgramOp(index=0, name="l0.attn", kernel="decode_attention",
                   in_region=0, out_region=1, k_region=2, v_region=3,
                   k_cache_region=4, v_cache_region=5,
                   attn=AttentionSpec(heads=H, kv_heads=KV, head_dim=hd,
                                      rope_theta=10000.0, block_q=1,
                                      block_kv=rows, kv_fold=fold))

    def layer(src, k_src, v_src, ck, cv, pos, live):
        return _run_decode_attention(op, src, k_src, v_src, ck, cv, pos,
                                     live, impl="pallas", interpret=False)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((slots, H * hd), BF16), ((slots, KV * hd), BF16),
        ((slots, KV * hd), BF16), (shape, BF16), (shape, BF16),
        ((slots,), jnp.int32), ((slots,), jnp.bool_))]
    text = (jax.jit(layer, donate_argnums=(3, 4)).lower(*args).compile()
            .as_text())
    assert "tpu_custom_call" in text
    region = slots * rows * KV * hd
    moved = [m.group(0) for m in _MOVE.finditer(text)
             if math.prod(int(d) for d in m.group(2).split(",") if d)
             == region]
    if lane_dense:
        assert not moved, moved
    else:
        assert moved


@pytest.mark.parametrize("quant", [False, True])
def test_paged_decode_attention_compiles(one_chip, quant):
    # A small pool: 8 slots x 8 pages of 16 rows.
    dt = jnp.int8 if quant else BF16

    def fn(q, kp, vp, table, n, ks, vs):
        return paged_decode_attention(
            q, kp, vp, table, kv_len=n, k_scale=ks if quant else None,
            v_scale=vs if quant else None, **KW)

    _compile(fn, one_chip, ((8, 15, 64), BF16), ((65, 16, 5, 64), dt),
             ((65, 16, 5, 64), dt), ((8, 8), jnp.int32), ((8,), jnp.int32),
             ((65,), F32), ((65,), F32))


@pytest.mark.parametrize("H,Cin,Cout,k,s,p,pool,dt", [
    (56, 64, 64, 3, 1, 1, None, BF16),       # resnet18 stage-1 3x3
    (56, 64, 128, 3, 2, 1, None, BF16),      # resnet18 stage-2 downsample
    (224, 3, 64, 11, 4, 2, None, BF16),      # alexnet-owt conv1
    (224, 3, 64, 11, 4, 2, (3, 2), BF16),    # alexnet-owt conv1 + maxpool
    (224, 3, 64, 11, 4, 2, (3, 2), F32),     # ... in f32, as the CNN serves
    (27, 64, 192, 5, 1, 2, (3, 2), F32),     # alexnet-owt conv2 + maxpool
])
def test_conv2d_compiles(one_chip, H, Cin, Cout, k, s, p, pool, dt):
    _compile(lambda x, w, b: conv2d(x, w, stride=s, pad=p, bias=b,
                                    activation="relu", fuse_pool=pool,
                                    strip_storage="virtual", **KW),
             one_chip, ((8, H, H, Cin), dt), ((k, k, Cin, Cout), dt),
             ((Cout,), dt))


def test_mamba2_scan_compiles(one_chip):
    # mamba2 widths: d_inner 5120 = 80 heads x 64, state 128.
    _compile(lambda x, dt, a, b, c: mamba2_scan(x, dt, a, b, c, **KW),
             one_chip, ((1, 512, 80, 64), BF16), ((1, 512, 80), F32),
             ((80,), F32), ((1, 512, 128), BF16), ((1, 512, 128), BF16))


def test_wkv6_compiles(one_chip):
    # rwkv6-7b widths: 64 heads x 64.
    _compile(lambda r, k, v, w, u: wkv6(r, k, v, w, u, **KW), one_chip,
             ((1, 512, 64, 64), BF16), ((1, 512, 64, 64), BF16),
             ((1, 512, 64, 64), BF16), ((1, 512, 64, 64), F32),
             ((64, 64), F32))
