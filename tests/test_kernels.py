"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dataflow import Dataflow
from repro.kernels import (attention_ref, conv2d, conv2d_ref,
                           decode_attention, decode_attention_ref,
                           flash_ref, flash_attention, matmul, matmul_ref,
                           mamba2_scan, mamba2_scan_ref, wkv6, wkv6_ref)
from repro.kernels.mamba2 import mamba2_decode_step
from repro.kernels.rwkv6 import wkv6_decode_step

K0 = jax.random.PRNGKey(0)


def keys(n):
    return jax.random.split(K0, n)


# --- matmul ------------------------------------------------------------------------
@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (300, 520, 260),
                                   (64, 1000, 72), (1, 256, 512),
                                   (257, 129, 383)])
@pytest.mark.parametrize("dataflow", list(Dataflow))
def test_matmul_shapes_dataflows(M, K, N, dataflow):
    ks = keys(4)
    a = jax.random.normal(ks[0], (M, K), jnp.float32)
    b = jax.random.normal(ks[1], (K, N), jnp.float32)
    out = matmul(a, b, impl="pallas", dataflow=dataflow,
                 block=(128, 128, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_ref(a, b)),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_fused_epilogue(dtype):
    ks = keys(4)
    a = jax.random.normal(ks[0], (192, 256), dtype)
    b = jax.random.normal(ks[1], (256, 160), dtype)
    bias = jax.random.normal(ks[2], (160,), dtype)
    byp = jax.random.normal(ks[3], (192, 160), dtype)
    for act in (None, "relu", "silu", "gelu"):
        out = matmul(a, b, bias=bias, activation=act, bypass=byp,
                     impl="pallas", dataflow=Dataflow.OUTPUT_STATIONARY,
                     block=(128, 128, 128), interpret=True)
        ref = matmul_ref(a, b, bias=bias, activation=act, bypass=byp)
        tol = 1e-3 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol)


def test_matmul_batched_lead_dims():
    ks = keys(2)
    a = jax.random.normal(ks[0], (2, 3, 64, 96), jnp.float32)
    b = jax.random.normal(ks[1], (96, 80), jnp.float32)
    out = matmul(a, b, impl="pallas", dataflow=Dataflow.MAPS_RESIDENT,
                 block=(128, 128, 128), interpret=True)
    assert out.shape == (2, 3, 64, 80)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b),
                               rtol=2e-4, atol=2e-4)


# --- conv2d ------------------------------------------------------------------------
@pytest.mark.parametrize("H,W,Cin,Cout,k,s,p", [
    (29, 31, 16, 24, 3, 2, 1),      # odd sizes, stride 2
    (27, 27, 8, 16, 5, 1, 2),       # AlexNet conv2 shape family
    (16, 16, 4, 8, 1, 1, 0),        # 1x1
    (56, 56, 16, 16, 3, 1, 1),      # ResNet block shape family
    (13, 13, 32, 16, 3, 1, 1),
])
@pytest.mark.parametrize("dataflow", [Dataflow.MAPS_RESIDENT,
                                      Dataflow.WEIGHTS_RESIDENT])
def test_conv2d_sweep(H, W, Cin, Cout, k, s, p, dataflow):
    ks = keys(4)
    x = jax.random.normal(ks[0], (2, H, W, Cin), jnp.float32)
    w = jax.random.normal(ks[1], (k, k, Cin, Cout), jnp.float32) * 0.2
    b = jax.random.normal(ks[2], (Cout,), jnp.float32) * 0.1
    ref = conv2d_ref(x, w, stride=s, pad=p, bias=b, activation="relu")
    byp = jax.random.normal(ks[3], ref.shape, jnp.float32)
    out = conv2d(x, w, stride=s, pad=p, bias=b, activation="relu",
                 bypass=byp, impl="pallas", interpret=True,
                 dataflow=dataflow)
    ref2 = conv2d_ref(x, w, stride=s, pad=p, bias=b, activation="relu",
                      bypass=byp)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref2),
                               rtol=1e-3, atol=1e-3)


def test_conv2d_bypass_first_resnet_order():
    ks = keys(3)
    x = jax.random.normal(ks[0], (1, 16, 16, 8), jnp.float32)
    w = jax.random.normal(ks[1], (3, 3, 8, 8), jnp.float32) * 0.2
    byp = jax.random.normal(ks[2], (1, 16, 16, 8), jnp.float32)
    out = conv2d(x, w, pad=1, activation="relu", bypass=byp,
                 bypass_first=True, impl="pallas", interpret=True)
    ref = conv2d_ref(x, w, pad=1, activation="relu", bypass=byp,
                     bypass_first=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


# --- flash attention -----------------------------------------------------------------
@pytest.mark.parametrize("Hq,Hkv", [(4, 4), (4, 2), (6, 1)])
@pytest.mark.parametrize("causal,window", [(False, None), (True, None),
                                           (True, 48)])
def test_flash_attention_gqa_masks(Hq, Hkv, causal, window):
    B, S, D = 2, 192, 32
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hq, S, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          impl="pallas", block_q=64, block_kv=64,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    fr = flash_ref(q, k, v, causal=causal, window=window, chunk=64)
    np.testing.assert_allclose(np.asarray(fr), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_cross_attention_unequal_seq():
    B, Hq, Hkv, Sq, Skv, D = 1, 4, 2, 96, 160, 32
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Skv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Skv, D), jnp.float32)
    ref = attention_ref(q, k, v)
    out = flash_attention(q, k, v, impl="pallas", block_q=32,
                          block_kv=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_ref_gradients_match_naive():
    B, H, S, D = 1, 2, 64, 16
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32) * 0.5
    k = jax.random.normal(ks[1], (B, H, S, D), jnp.float32) * 0.5
    v = jax.random.normal(ks[2], (B, H, S, D), jnp.float32)
    g1 = jax.grad(lambda q, k, v: (attention_ref(q, k, v, causal=True)
                                   ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda q, k, v: (flash_ref(q, k, v, causal=True,
                                             chunk=16) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


# --- decode attention ----------------------------------------------------------------
@pytest.mark.parametrize("S,block", [(256, 64), (384, 128), (128, 128)])
def test_decode_attention_varlen(S, block):
    B, Hq, Hkv, D = 3, 8, 2, 64
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32) * 0.3
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    kvl = jnp.array([S, S // 2, 7], jnp.int32)
    ref = decode_attention_ref(q, k, v, kv_len=kvl)
    out = decode_attention(q, k, v, kv_len=kvl, impl="pallas",
                           block_kv=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("D,fold,block,dtype", [
    (64, 2, 128, jnp.float32),     # smollm-360m's head_dim: 2 rows a lane row
    (64, 2, 256, jnp.bfloat16),    # ... in the cache dtype it serves
    (128, 1, 128, jnp.float32),    # head_dim 128: plain head-major
    (16, 8, 64, jnp.float32),      # the smoke configs' head_dim
])
def test_decode_attention_folded_cache(D, fold, block, dtype):
    """The kernel reads the lane-dense cache (B, Hkv, S/f, f*D) as it
    rests and matches the oracle on the unfolded cache, GQA group 3.
    Slots: an odd length (its last packed row half filled), a full ring
    as after a wrap (kv_len == cache_len), a slot holding one row (a
    fresh slot, or a dead one decode left unwritten), and one ending on
    a block boundary."""
    from repro.kernels.decode_attention import fold_kv
    B, Hkv, G, S = 4, 2, 3, 256
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hkv * G, D), jnp.float32)
    k = (jax.random.normal(ks[1], (B, Hkv, S, D)) * 0.3).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(dtype)
    kvl = jnp.array([S - 37, S, 1, block], jnp.int32)
    ref = decode_attention_ref(q, k, v, kv_len=kvl)
    kf, vf = fold_kv(k, fold), fold_kv(v, fold)
    assert kf.shape == (B, Hkv, S // fold, fold * D)
    out = decode_attention(q, kf, vf, kv_len=kvl, fold=fold,
                           impl="pallas", block_kv=block, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    # the reference path unfolds the same operand
    np.testing.assert_array_equal(
        np.asarray(decode_attention(q, kf, vf, kv_len=kvl, fold=fold,
                                    impl="reference")),
        np.asarray(ref))


def test_decode_attention_fp8_cache():
    B, Hq, Hkv, S, D = 2, 4, 2, 128, 32
    ks = keys(3)
    q = jax.random.normal(ks[0], (B, Hq, D), jnp.float32)
    k = (jax.random.normal(ks[1], (B, Hkv, S, D)) * 0.3
         ).astype(jnp.float8_e4m3fn)
    v = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(jnp.float8_e4m3fn)
    ref = decode_attention_ref(q, k.astype(jnp.float32),
                               v.astype(jnp.float32))
    out = decode_attention(q, k, v, impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)


# --- mamba2 --------------------------------------------------------------------------
@pytest.mark.parametrize("L,chunk", [(128, 32), (256, 64), (64, 64)])
def test_mamba2_chunked_vs_sequential(L, chunk):
    Bt, H, P, N = 2, 3, 32, 16
    ks = keys(6)
    x = jax.random.normal(ks[0], (Bt, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, L, N)) * 0.5
    C = jax.random.normal(ks[4], (Bt, L, N)) * 0.5
    D = jax.random.normal(ks[5], (H,)) * 0.1
    yr, hr = mamba2_scan_ref(x, dt, A, B, C, D_skip=D, return_state=True)
    yp, hp = mamba2_scan(x, dt, A, B, C, D_skip=D, return_state=True,
                         impl="pallas", chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(hr),
                               rtol=1e-4, atol=1e-4)


def test_mamba2_state_carry_and_decode():
    """Scan over [0:L1] then decode steps == full scan (streaming)."""
    Bt, L, H, P, N = 1, 32, 2, 16, 8
    ks = keys(5)
    x = jax.random.normal(ks[0], (Bt, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bt, L, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    B = jax.random.normal(ks[3], (Bt, L, N)) * 0.5
    C = jax.random.normal(ks[4], (Bt, L, N)) * 0.5
    y_full = mamba2_scan_ref(x, dt, A, B, C)
    L1 = 24
    y1, h = mamba2_scan_ref(x[:, :L1], dt[:, :L1], A, B[:, :L1],
                            C[:, :L1], return_state=True)
    ys = [y1]
    for t in range(L1, L):
        yt, h = mamba2_decode_step(h, x[:, t], dt[:, t], A, B[:, t],
                                   C[:, t])
        ys.append(yt[:, None])
    y_stream = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_stream), np.asarray(y_full),
                               rtol=1e-4, atol=1e-4)


# --- rwkv6 ---------------------------------------------------------------------------
@pytest.mark.parametrize("L,chunk", [(64, 16), (128, 64)])
def test_wkv6_vs_sequential(L, chunk):
    B, H, D = 2, 2, 32
    ks = keys(5)
    r = jax.random.normal(ks[0], (B, L, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, L, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, L, H, D))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, L, H, D)) * 0.5))
    u = jax.random.normal(ks[4], (H, D)) * 0.3
    yr, sr = wkv6_ref(r, k, v, w, u, return_state=True)
    yp, sp = wkv6(r, k, v, w, u, return_state=True, impl="pallas",
                  chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_decode_streaming():
    B, L, H, D = 1, 24, 2, 16
    ks = keys(5)
    r = jax.random.normal(ks[0], (B, L, H, D)) * 0.5
    k = jax.random.normal(ks[1], (B, L, H, D)) * 0.5
    v = jax.random.normal(ks[2], (B, L, H, D))
    w = jnp.exp(-jnp.exp(jax.random.normal(ks[3], (B, L, H, D)) * 0.5))
    u = jax.random.normal(ks[4], (H, D)) * 0.3
    y_full = wkv6_ref(r, k, v, w, u)
    S = jnp.zeros((B, H, D, D))
    ys = []
    for t in range(L):
        yt, S = wkv6_decode_step(S, r[:, t], k[:, t], v[:, t], w[:, t], u)
        ys.append(yt[:, None])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(ys, 1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Hq,Hkv,causal,window", [
    (4, 4, True, None), (4, 2, True, None),
    (4, 2, False, None), (6, 2, True, 48)])
def test_flash_pallas_backward_kernels(Hq, Hkv, causal, window):
    """Pallas dq/dkv kernels (bwd_kernel.py) vs naive autodiff."""
    B, S, D = 2, 128, 32
    ks = keys(4)
    q = jax.random.normal(ks[0], (B, Hq, S, D)) * 0.5
    k = jax.random.normal(ks[1], (B, Hkv, S, D)) * 0.5
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    dO = jax.random.normal(ks[3], (B, Hq, S, D))

    def loss_p(q, k, v):
        o = flash_attention(q, k, v, causal=causal, window=window,
                            impl="pallas_trainable", block_q=32,
                            block_kv=32, interpret=True)
        return jnp.sum(o * dO)

    def loss_r(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal,
                                     window=window) * dO)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
