#!/usr/bin/env python3
"""Bring-up check of the compiled-Program serving path on a TPU v5e.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # the sharded trainer, 2x2 mesh

On one chip the phases run in order:

1. device: JAX's first device is the chip ``core/hw.py::TPU_V5E``
   describes;
2. kernels: every main-path Pallas kernel at real widths, compiled by
   Mosaic (``impl="pallas", interpret=False``), against its oracle;
3. cnn: alexnet-owt at 224x224 served through ``ServingEngine``'s CNN
   Program path, logits against ``models/cnn.py::reference_forward``;
4. lm: smollm-360m at its published widths served through the stateful
   (prefill, decode) Program pair, prefill logits against the same
   Program run on reference kernels in float32.

``--four-chips`` runs only the sharded train step of ``launch/train.py``
on the (data, model) = (2, 2) mesh, and the same steps on one of those
chips for comparison.

Weights and data are random, made from ``--seed``.  Any failed phase
raises, so the process exits non-zero; so does a run where JAX finds no
TPU.  The last line of stdout is one JSON object naming the device.
Compile and first-call seconds are set-up time, not device metrics.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

# Max |got - ref| / max |ref| a kernel may reach against its float32
# oracle (run at matmul precision "highest"), by the kernel's dtype.
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 1e-2}
CNN_TOL = 1e-2          # alexnet-owt float32, 8 layers end to end
LM_TOL = 5e-2           # smollm-360m bfloat16, 32 layers end to end


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: the repro sources are not at {SRC}")
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")

    dev = phase("device", check_device, 4 if args.four_chips else 1)
    if args.four_chips:
        phase("train_4chips", train_four_chips, args.seed)
    else:
        phase("kernels", kernels, args.seed)
        phase("cnn_serving", cnn_serving, args.seed)
        phase("lm_serving", lm_serving, args.seed)
    print(json.dumps({"ok": True, "device": dev}))


def phase(name, fn, *args):
    print(f"== phase {name}", flush=True)
    t0 = time.perf_counter()
    out = fn(*args)
    print(f"== phase {name}: PASS (set-up and run "
          f"{time.perf_counter() - t0:.1f} s)", flush=True)
    return out


def check_device(want: int) -> dict:
    import jax
    from repro.core.hw import TPU_V5E
    from repro.kernels.common import default_interpret
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    print(f"  devices: {info}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU ({d.platform})")
    if d.device_kind != TPU_V5E.device_kind:
        raise SystemExit(f"chip_smoke: device {d.device_kind!r} is not the "
                         f"{TPU_V5E.device_kind!r} core/hw.py describes")
    if len(devs) != want:
        raise SystemExit(f"chip_smoke: this phase set needs {want} "
                         f"device(s), JAX has {len(devs)}")
    if default_interpret():
        raise SystemExit("chip_smoke: Pallas would run in interpret mode")
    return info


# --- helpers -----------------------------------------------------------------
def compare(name, got, ref, tol) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {ref.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite output")
    err = float(np.abs(got - ref).max())
    scale = float(np.abs(ref).max()) or 1.0
    rel = err / scale
    verdict = "ok" if rel <= tol else "FAIL"
    print(f"  {name}: max_abs_err={err:.4e} max_abs_ref={scale:.4e} "
          f"rel_err={rel:.4e} tol={tol:.0e} {verdict}", flush=True)
    if rel > tol:
        raise AssertionError(f"{name}: relative error {rel:.3e} > {tol:.0e}")
    return rel


def timed_compile(name, fn, *args):
    """AOT-compile ``fn`` for ``args`` and run it once; prints set-up
    seconds (compile, first call)."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    t2 = time.perf_counter()
    print(f"  {name}: set-up compile_s={t1 - t0:.2f} "
          f"first_call_s={t2 - t1:.2f}", flush=True)
    return out


def highest(fn, *args):
    """Run the float32 oracle at full matmul precision."""
    import jax
    import jax.numpy as jnp
    args = jax.tree.map(
        lambda a: a.astype(jnp.float32)
        if jnp.issubdtype(a.dtype, jnp.floating) else a, args)
    with jax.default_matmul_precision("highest"):
        return jax.block_until_ready(jax.jit(fn)(*args))


# --- phase: kernels ----------------------------------------------------------
def kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import (attention_ref, conv2d, conv2d_ref,
                               decode_attention, decode_attention_ref,
                               flash_attention, mamba2_scan, mamba2_scan_ref,
                               matmul, matmul_ref, maxpool2d_ref,
                               paged_decode_attention, wkv6, wkv6_ref)
    from repro.kernels.decode_attention import fold_kv, gather_pages

    bf16, f32 = jnp.bfloat16, jnp.float32
    KW = dict(impl="pallas", interpret=False)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))

    def rnd(shape, dtype, scale=1.0):
        x = jax.random.normal(next(keys), shape, f32) * scale
        return x.astype(dtype)

    def run(name, kernel, ref, *args):
        out = timed_compile(name, kernel, *args)
        want = highest(ref, *args)
        dtype = jax.tree.leaves(out)[0].dtype
        for i, (o, r) in enumerate(zip(jax.tree.leaves(out),
                                       jax.tree.leaves(want))):
            compare(f"{name}[{i}]" if i else name, o, r,
                    KERNEL_TOL[jnp.dtype(dtype).name])

    # matmul: smollm-360m q projection (256-row chunk) and prefill lm_head.
    for M, K, N in ((256, 960, 960), (2048, 960, 49152)):
        run(f"matmul {M}x{K}x{N}", lambda a, b: matmul(a, b, **KW),
            lambda a, b: matmul_ref(a, b), rnd((M, K), bf16),
            rnd((K, N), bf16, K ** -0.5))

    # conv2d: resnet stride 1 and 2 (bf16), alexnet-owt conv1 stride 4
    # alone and with its fused 3x3/2 maxpool (float32, as the CNN serves).
    for name, B, H, Cin, Cout, k, s, p, pool, dt in (
            ("conv2d s1 resnet 56x56x64", 8, 56, 64, 64, 3, 1, 1, None, bf16),
            ("conv2d s2 resnet 56x56x64", 8, 56, 64, 128, 3, 2, 1, None, bf16),
            ("conv2d s4 alexnet conv1", 8, 224, 3, 64, 11, 4, 2, None, f32),
            ("conv2d s4 alexnet conv1+pool", 8, 224, 3, 64, 11, 4, 2, (3, 2),
             f32)):
        def ker(x, w, b, s=s, p=p, pool=pool):
            return conv2d(x, w, stride=s, pad=p, bias=b, activation="relu",
                          fuse_pool=pool, **KW)

        def ref(x, w, b, s=s, p=p, pool=pool):
            y = conv2d_ref(x, w, stride=s, pad=p, bias=b, activation="relu")
            return maxpool2d_ref(y, window=pool[0], stride=pool[1]) \
                if pool else y
        run(name, ker, ref, rnd((B, H, H, Cin), dt),
            rnd((k, k, Cin, Cout), dt, (k * k * Cin) ** -0.5),
            rnd((Cout,), dt, 0.1))

    # flash attention: smollm-360m causal prefill, S=2048, GQA 15/5.
    run("flash_attention causal 15/5 S=2048",
        lambda q, k, v: flash_attention(q, k, v, causal=True, **KW),
        lambda q, k, v: attention_ref(q, k, v, causal=True),
        rnd((1, 15, 2048, 64), bf16), rnd((1, 5, 2048, 64), bf16),
        rnd((1, 5, 2048, 64), bf16))

    # decode attention: 8 slots x 2048 cache rows, ragged lengths, on the
    # head-major cache and on the lane-dense one the KV regions hold.
    lens = jax.random.randint(next(keys), (8,), 1, 2049, jnp.int32)
    q, k, v = (rnd((8, 15, 64), bf16), rnd((8, 5, 2048, 64), bf16),
               rnd((8, 5, 2048, 64), bf16))
    for fold in (1, 2):
        run(f"decode_attention 8x2048 fold={fold}",
            lambda q, k, v, n, f=fold: decode_attention(
                q, fold_kv(k, f), fold_kv(v, f), kv_len=n, fold=f, **KW),
            lambda q, k, v, n: decode_attention_ref(q, k, v, kv_len=n,
                                                    scale=64 ** -0.5),
            q, k, v, lens)

    # paged decode at a small pool: 8 slots x 8 pages of 16 rows; float
    # and int8 pages.
    table = jax.random.permutation(next(keys), 64)[:64].reshape(8, 8) + 1
    plen = jax.random.randint(next(keys), (8,), 1, 129, jnp.int32)
    q = rnd((8, 15, 64), bf16)

    def paged_ref(q, kp, vp, t, n, ks=None, vs=None):
        return decode_attention_ref(q, gather_pages(kp, t, ks),
                                    gather_pages(vp, t, vs), kv_len=n,
                                    scale=64 ** -0.5)
    run("paged_decode_attention bf16 pages",
        lambda q, kp, vp, t, n: paged_decode_attention(
            q, kp, vp, t, kv_len=n, **KW),
        paged_ref, q, rnd((65, 16, 5, 64), bf16), rnd((65, 16, 5, 64), bf16),
        table.astype(jnp.int32), plen)
    kq = jax.random.randint(next(keys), (65, 16, 5, 64), -127, 128, jnp.int32)
    vq = jax.random.randint(next(keys), (65, 16, 5, 64), -127, 128, jnp.int32)
    run("paged_decode_attention int8 pages",
        lambda q, kp, vp, t, n, ks, vs: paged_decode_attention(
            q, kp, vp, t, kv_len=n, k_scale=ks, v_scale=vs, **KW),
        paged_ref, q, kq.astype(jnp.int8), vq.astype(jnp.int8),
        table.astype(jnp.int32), plen,
        jnp.full((65,), 1 / 127, f32), jnp.full((65,), 1 / 127, f32))

    # mamba2 SSD scan at mamba2 widths (80 heads x 64, state 128).
    L, H, P, N = 512, 80, 64, 128
    run("mamba2_scan 512x80x64 N=128",
        lambda x, dt, a, b, c: mamba2_scan(x, dt, a, b, c,
                                           return_state=True, **KW),
        lambda x, dt, a, b, c: mamba2_scan_ref(x, dt, a, b, c,
                                               return_state=True),
        rnd((1, L, H, P), bf16, 0.5),
        jax.nn.softplus(rnd((1, L, H), f32)) * 0.1,
        -jnp.exp(rnd((H,), f32)), rnd((1, L, N), bf16, 0.5),
        rnd((1, L, N), bf16, 0.5))

    # wkv6 at rwkv6-7b widths (64 heads x 64).
    L, H, D = 512, 64, 64
    run("wkv6 512x64x64",
        lambda r, k, v, w, u: wkv6(r, k, v, w, u, return_state=True, **KW),
        lambda r, k, v, w, u: wkv6_ref(r, k, v, w, u, return_state=True),
        rnd((1, L, H, D), bf16, 0.5), rnd((1, L, H, D), bf16, 0.5),
        rnd((1, L, H, D), bf16),
        jnp.exp(-jnp.exp(rnd((1, L, H, D), f32, 0.5))),
        rnd((H, D), f32, 0.3))


# --- phase: CNN serving ------------------------------------------------------
def cnn_serving(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import CNN_REGISTRY
    from repro.models import cnn, init_params
    from repro.runtime import executor
    from repro.serving import Request, ServingEngine

    # As launch/serve.py::_serve_cnn builds it, with the kernels named.
    cfg = CNN_REGISTRY["alexnet-owt"]
    params = init_params(cnn.param_defs(cfg), jax.random.PRNGKey(seed))
    eng = ServingEngine(cfg, params, slots=8, impl="pallas")
    convs = [op for op in eng.program.ops if op.kernel == "conv2d"]
    print(f"  program: {len(eng.program.ops)} ops, {len(convs)} conv2d "
          f"(strip storage {sorted({op.strip_storage for op in convs})}, "
          f"{sum(op.fuse_pool is not None for op in convs)} with fused pool)")
    rng = np.random.default_rng(seed)
    images = rng.standard_normal(
        (12, cfg.input_hw, cfg.input_hw, cfg.input_ch)).astype(np.float32)
    for i, img in enumerate(images):
        eng.submit(Request(uid=i, prompt=img))
    t0 = time.perf_counter()
    done = sorted(eng.run_until_drained(), key=lambda r: r.uid)
    print(f"  served {len(done)} images; set-up and serve "
          f"{time.perf_counter() - t0:.2f} s")
    if [r.uid for r in done] != list(range(12)) or any(
            len(r.out_tokens) != 1 for r in done):
        raise AssertionError("not every image got one class")
    # The engine's own compiled Program (same runner cache entry).
    logits = executor.jitted_runner(eng.program, impl="pallas")(
        params, jnp.asarray(images[:8]))
    ref = highest(lambda p, x: cnn.reference_forward(p, x, cfg), params,
                  jnp.asarray(images[:8]))
    compare("alexnet-owt logits vs reference_forward", logits, ref, CNN_TOL)
    served = [r.out_tokens[0] for r in done[:8]]
    if served != [int(c) for c in np.argmax(np.asarray(logits), -1)]:
        raise AssertionError("served classes differ from the Program's "
                             "logits")
    agree = np.mean(np.argmax(np.asarray(logits), -1)
                    == np.argmax(np.asarray(ref), -1))
    print(f"  top-1 agreement with the reference: {agree:.3f}")


# --- phase: LM serving -------------------------------------------------------
def lm_serving(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.models import get_model, init_params
    from repro.runtime import executor
    from repro.serving import Request, ServingEngine

    cfg = get_config("smollm-360m")          # published widths, no .smoke()
    slots, max_len, new = 8, 2048, 32
    params = init_params(get_model(cfg).param_defs(cfg),
                         jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, slots=slots, max_len=max_len,
                        use_program=True, impl="pallas")
    if not eng.on_program_path:
        raise SystemExit(f"chip_smoke: smollm-360m is not on the Program "
                         f"path: {eng.fallback_reason}")
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=int(n)).astype(np.int32)
               for n in rng.integers(200, 700, size=8)]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new_tokens=new))
    done = sorted(eng.run_until_drained(), key=lambda r: r.uid)
    print(f"  served {len(done)} requests (prompts "
          f"{[len(p) for p in prompts]}), {eng.n_prefills} prefills, "
          f"{eng.n_decode_ticks} decode ticks, prefill_recomputes="
          f"{eng.n_prefill_recomputes}; set-up and serve "
          f"{time.perf_counter() - t0:.2f} s", flush=True)
    if [r.uid for r in done] != list(range(8)):
        raise AssertionError("not every request finished")
    if any(len(r.out_tokens) != new for r in done):
        raise AssertionError(
            f"token counts {[len(r.out_tokens) for r in done]}")
    if eng.n_prefill_recomputes != 0:
        raise AssertionError("the Program path recomputed a prefill")

    # Prefill logits of request 0: the engine's compiled prefill (same
    # runner cache entry) against the same Program on reference kernels.
    prefill = eng.program.prefill
    n = len(prompts[0])
    tokens = np.zeros((1, max_len), np.int32)
    tokens[0, :n] = prompts[0]
    tokens = jnp.asarray(tokens)
    run = executor.jitted_prefill_runner(prefill, impl="pallas")
    logits, _ = run(params, tokens, executor.init_program_state(eng.program),
                    0, n, 0)
    logits = np.asarray(logits[0, :n], np.float32)
    ref = highest(lambda p, t, s: executor.run_prefill(
        prefill, p, t, s, 0, n, impl="reference")[0],
        params, tokens, executor.init_program_state(eng.program))
    ref = np.asarray(ref[0, :n])
    compare("smollm-360m prefill logits vs float32 reference", logits, ref,
            LM_TOL)
    if done[0].out_tokens[0] != int(np.argmax(logits[n - 1])):
        raise AssertionError("served first token differs from the prefill "
                             "logits")
    agree = np.mean(np.argmax(logits, -1) == np.argmax(ref, -1))
    print(f"  prefill argmax agreement with the reference: {agree:.3f}")


# --- phase: four chips -------------------------------------------------------
def train_four_chips(seed: int, steps: int = 3) -> None:
    """launch/train.py's sharded step on the (2, 2) mesh, then on one of
    those chips; loss per step must agree and the parameters must be
    split over the model axis."""
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_mesh_from_descriptor
    from repro.launch.steps import build_step
    from repro.launch.train import train_mesh
    from repro.models import get_model, init_params
    from repro.optim import AdamW
    from repro.parallel.rules import make_plan

    cfg = get_config("smollm-360m")
    shape = ShapeSpec("chip_smoke_train", 512, 8, "train")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=shape.seq_len,
                       global_batch=shape.global_batch, seed=seed)
    losses = {}
    for n_dev in (4, 1):
        desc = train_mesh(n_dev)
        mesh = make_mesh_from_descriptor(desc)
        plan = make_plan(cfg, shape, desc, "auto")
        optimizer = AdamW(lr=1e-3)
        with mesh:
            bundle = build_step(cfg, shape, plan, mesh, optimizer=optimizer,
                                impl="pallas_trainable")
            params = init_params(get_model(cfg).param_defs(cfg),
                                 jax.random.PRNGKey(seed))
            opt_state = optimizer.init(params)
            losses[n_dev] = []
            for step in range(steps):
                t0 = time.perf_counter()
                params, opt_state, m = bundle.fn(params, opt_state,
                                                 data.batch_at(step))
                losses[n_dev].append(float(m["loss"]))
                print(f"  mesh {desc.shape} ({plan.strategy}) step {step}: "
                      f"loss={losses[n_dev][-1]:.6f} "
                      f"({'set-up and ' if step == 0 else ''}step "
                      f"{time.perf_counter() - t0:.2f} s)", flush=True)
            if n_dev == 4:
                split_over_model_axis(params, mesh)
        del params, opt_state
    l4, l1 = np.asarray(losses[4]), np.asarray(losses[1])
    if not np.isfinite(l4).all():
        raise AssertionError(f"non-finite loss on 4 chips: {l4}")
    rel = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
    print(f"  loss 4 chips {l4.tolist()} vs 1 chip {l1.tolist()}: "
          f"max rel diff {rel:.3e} (tol {KERNEL_TOL['bfloat16']:.0e})")
    if rel > KERNEL_TOL["bfloat16"]:
        raise AssertionError(f"losses disagree: {rel:.3e}")


def split_over_model_axis(params, mesh) -> None:
    import jax
    devs = list(mesh.devices.flat)
    held = {d: 0 for d in devs}
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            held[sh.device] += sh.data.nbytes
    for d in devs:
        st = d.memory_stats() or {}
        print(f"  device {d.id}: param bytes {held[d]} of {total} "
              f"({held[d] / total:.3f}); memory_stats bytes_in_use="
              f"{st.get('bytes_in_use')} peak={st.get('peak_bytes_in_use')}")
    if max(held.values()) > 0.75 * total:
        raise AssertionError("parameters are not split over the model axis")


if __name__ == "__main__":
    main()
