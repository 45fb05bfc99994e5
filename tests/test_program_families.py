"""Per-family Program parity: generic named persistent state.

Every registered state family — MoE (capacity-bucketed expert
dispatch), pure SSM (mamba2), rwkv6 recurrence, zamba2 hybrid
(SSM + shared windowed attention), whisper encoder-decoder
(cross-attention over read-only encoder memory) — compiles to the same
(prefill, decode) Program pair and matches its legacy cache loop at
<=1e-5, with persistent regions minted through the one generic
``regions.state_specs`` hook.

Oracle note (MoE): the legacy *batched* forward routes every
sequence's tokens jointly through the capacity buckets, so it is NOT a
per-request oracle.  Teacher-forcing the legacy ``decode_step`` routes
exactly the Program's token batches (slots per tick), and at smoke
scale no expert ever exceeds its capacity, so parity is exact."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.core import regions
from repro.models import get_model, init_params, transformer
from repro.runtime import executor

K0 = jax.random.PRNGKey(0)

FAMILY_ARCHS = ["granite-moe-1b-a400m", "mamba2", "rwkv6-7b",
                "zamba2-7b", "whisper-base"]


def _setup(name, slots=2, max_len=16, **over):
    cfg = REGISTRY[name].smoke()
    if over:
        cfg = dataclasses.replace(cfg, **over)
    api = get_model(cfg)
    params = init_params(api.param_defs(cfg), K0)
    pair = transformer.compile_program_pair(cfg, slots=slots,
                                            max_len=max_len)
    state = executor.init_program_state(pair)
    return cfg, api, params, pair, state


def _write_memory(api, cfg, params, pair, state, cache, slot, frames):
    """Admission-time write of read-only encoder memory: scatter the
    ``encode_memory`` rows into the Program state at ``slot`` AND into
    the legacy cache's cross K/V (same source, both sides of the
    parity check)."""
    rows = api.encode_memory(params, jnp.asarray(frames), cfg,
                             impl="reference")
    for nm, row in rows.items():
        rid = pair.persistent[nm]
        buf = state.caches[rid]
        state.caches[rid] = buf.at[slot].set(row.astype(buf.dtype))
    for i in range(cfg.n_layers):
        for side in ("k", "v"):
            leg = cache[f"cross_{side}"]
            row = rows[f"l{i}.cross_{side}"]          # (Te, KV, hd)
            cache[f"cross_{side}"] = leg.at[i, slot].set(
                row.transpose(1, 0, 2).astype(leg.dtype))
    return state, cache


def _prefill_slot(pair, params, state, slot, prompt, max_len):
    padded = np.zeros((1, max_len), np.int32)
    padded[0, :len(prompt)] = prompt
    return executor.run_prefill(pair.prefill, params,
                                jnp.asarray(padded), state, slot,
                                len(prompt), impl="reference")


# --- prefill + N-decode parity vs each family's legacy cache loop ------------------
@pytest.mark.parametrize("name", FAMILY_ARCHS)
def test_family_prefill_decode_parity(name):
    """Program prefill + N decode steps == teacher-forcing the same
    tokens through the family's legacy ``init_cache``/``decode_step``
    loop, logits <=1e-5 at every step."""
    slots, max_len, P, N = 2, 16, 5, 4
    cfg, api, params, pair, state = _setup(name, slots, max_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(slots, P)).astype(np.int32)

    cache = api.init_cache(cfg, slots, max_len)
    if api.extra_input == "encoder_frames":
        for s in range(slots):
            frames = rng.standard_normal(
                (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
            state, cache = _write_memory(api, cfg, params, pair, state,
                                         cache, s, frames)

    for t in range(P):
        leg_logits, cache = api.decode_step(
            params, cache, jnp.asarray(prompts[:, t]), cfg,
            impl="reference")

    for slot in range(slots):
        logits, state = _prefill_slot(pair, params, state, slot,
                                      prompts[slot], max_len)
        np.testing.assert_allclose(
            np.asarray(logits[0, P - 1], np.float32),
            np.asarray(leg_logits[slot], np.float32), rtol=0, atol=1e-5)
    assert list(np.asarray(state.lengths)) == [P] * slots

    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(N):
        leg_logits, cache = api.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(
            np.asarray(dec_logits, np.float32),
            np.asarray(leg_logits, np.float32), rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    assert list(np.asarray(state.lengths)) == [P + N] * slots


@pytest.mark.parametrize("name", ["mamba2", "rwkv6-7b", "zamba2-7b"])
def test_family_state_carries_past_max_len(name):
    """Recurrent state has no sequence axis, so decode runs straight
    past ``max_len``: lengths keep counting, the hybrid's attention
    ring rolls, and logits still match the legacy loop."""
    slots, max_len, P, N = 1, 8, 8, 4                 # P+N > max_len
    cfg, api, params, pair, state = _setup(name, slots, max_len)
    prompt = np.arange(1, P + 1, dtype=np.int32)
    cache = api.init_cache(cfg, slots, max_len)
    for t in range(P):
        leg_logits, cache = api.decode_step(
            params, cache, jnp.asarray(prompt[t:t + 1]), cfg,
            impl="reference")
    _, state = _prefill_slot(pair, params, state, 0, prompt, max_len)
    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(N):
        leg_logits, cache = api.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(
            np.asarray(dec_logits, np.float32),
            np.asarray(leg_logits, np.float32), rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    assert int(np.asarray(state.lengths)[0]) == P + N


def test_family_same_tick_slot_reuse():
    """A slot freed mid-tick (EOS/max_new on the prefill token) admits
    the next queued request in the same tick — family state is reset by
    the prefill, so recurrent leftovers cannot leak."""
    from repro.serving import Request, ServingEngine
    cfg = REGISTRY["rwkv6-7b"].smoke()
    params = init_params(get_model(cfg).param_defs(cfg), K0)
    eng = ServingEngine(cfg, params, slots=1, max_len=8,
                        impl="reference", use_program=True)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=np.asarray([5, 6], np.int32),
                           max_new_tokens=1))
    finished = eng.step()
    assert len(finished) == 2 and not eng.queue
    assert eng.n_prefills == 2 and eng.n_prefill_recomputes == 0


# --- region-plan units --------------------------------------------------------------
def test_ssm_state_regions_are_o1_in_seq_len():
    """Pure-recurrence families (ssm, hybrid-without-attention) mint
    persistent state with NO sequence axis: the specs are byte-for-byte
    identical at max_len 16 and 1024."""
    for name in ("rwkv6-7b", "mamba2"):
        cfg = REGISTRY[name].smoke()
        short, caps_s = regions.state_specs(cfg, 2, 16)
        long, caps_l = regions.state_specs(cfg, 2, 1024)
        assert short == long and caps_s == caps_l
    # the hybrid's SSM/conv specs are O(1) too; only the shared
    # attention KV rows scale (capped by the window)
    zcfg = REGISTRY["zamba2-7b"].smoke()
    zs, _ = regions.state_specs(zcfg, 2, 16)
    zl, _ = regions.state_specs(zcfg, 2, 1024)
    recur = lambda specs: [s for s in specs if "ssm" in s.name
                           or "conv" in s.name]
    assert recur(zs) == recur(zl)
    kv_rows = lambda specs: {s.name: regions.kv_cache_rows(s.shape, s.fold)
                             for s in specs if s not in recur(specs)}
    assert all(r == 16 for r in kv_rows(zs).values())
    assert all(r == min(1024, zcfg.attn_window)
               for r in kv_rows(zl).values())


def test_encoder_memory_pinned_read_only():
    """Whisper's cross K/V regions are marked read-only and the decode
    stream never scatters into them: after prefill + decode ticks the
    memory buffers are bitwise what admission wrote."""
    cfg = REGISTRY["whisper-base"].smoke()
    specs, caps = regions.state_specs(cfg, 2, 16)
    ro = {s.name for s in specs if s.read_only}
    assert ro == {f"l{i}.cross_{sd}" for i in range(cfg.n_layers)
                  for sd in ("k", "v")}
    assert not any(s.read_only for s in specs if "cross" not in s.name)

    api = get_model(cfg)
    cfg2, api, params, pair, state = _setup("whisper-base", 1, 16)
    cache = api.init_cache(cfg2, 1, 16)
    rng = np.random.default_rng(1)
    frames = rng.standard_normal(
        (cfg2.encoder_seq, cfg2.d_model)).astype(np.float32)
    state, cache = _write_memory(api, cfg2, params, pair, state, cache,
                                 0, frames)
    mem_rids = [pair.persistent[n] for n in ro]
    written = {rid: np.asarray(state.caches[rid]) for rid in mem_rids}
    _, state = _prefill_slot(pair, params, state, 0,
                             np.asarray([3, 1, 4], np.int32), 16)
    for _ in range(3):
        _, state = executor.run_decode(
            pair.decode, params, jnp.asarray([7], jnp.int32), state,
            impl="reference")
    for rid in mem_rids:
        np.testing.assert_array_equal(np.asarray(state.caches[rid]),
                                      written[rid])


def test_family_capability_table():
    """The per-family StateCaps matrix the serving gates consult
    (pinned here and documented in ARCHITECTURE.md Stage 6)."""
    expect = {
        "smollm-360m":          (True,  True,  True,  True),
        "granite-moe-1b-a400m": (True,  True,  False, False),
        "zamba2-7b":            (False, True,  False, False),
        "mamba2":               (False, True,  False, False),
        "rwkv6-7b":             (False, False, False, False),
        "whisper-base":         (False, False, False, False),
    }
    for name, (paged, windowed, chunk, spec) in expect.items():
        cfg = REGISTRY[name].smoke()
        _, caps = regions.state_specs(cfg, 2, 16)
        assert (caps.paged, caps.windowed, caps.chunkable,
                caps.speculatable) == (paged, windowed, chunk, spec), name


def test_state_specs_hook_validation():
    """The allocator rejects hooks whose specs drop the slot axis, and
    names the family when no hook is registered at all."""
    import types
    fake = types.SimpleNamespace(family="_test_fam", name="fake-cfg")

    def bad_hook(cfg, slots, max_len):
        return (regions.PersistentSpec("s", (3, 4), "float32", 48),), \
            regions.StateCaps()

    regions.register_state_family("_test_fam", bad_hook)
    try:
        with pytest.raises(ValueError, match="slot axis"):
            regions.state_specs(fake, 2, 16)
    finally:
        regions._STATE_FAMILIES.pop("_test_fam", None)
    missing = types.SimpleNamespace(family="_nope", name="fake-cfg")
    with pytest.raises(NotImplementedError, match="_nope"):
        regions.state_specs(missing, 2, 16)


# --- serving round trip -------------------------------------------------------------
def test_whisper_serving_round_trip():
    """Audio requests serve end-to-end on the Program path: admission
    encodes the request's frames into read-only memory, and a request
    without frames is refused loudly."""
    from repro.serving import Request, ServingEngine
    cfg = REGISTRY["whisper-base"].smoke()
    params = init_params(get_model(cfg).param_defs(cfg), K0)
    eng = ServingEngine(cfg, params, slots=2, max_len=16,
                        impl="reference", use_program=True)
    assert eng.on_program_path, eng.fallback_reason
    rng = np.random.default_rng(0)
    for i in range(2):
        frames = rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)
        eng.submit(Request(uid=i, prompt=np.asarray([4, 2], np.int32),
                           max_new_tokens=4, extra=frames))
    done = eng.run_until_drained()
    assert len(done) == 2
    assert all(len(r.out_tokens) == 4 for r in done)
    assert eng.n_prefill_recomputes == 0

    eng.submit(Request(uid=9, prompt=np.asarray([1], np.int32),
                       max_new_tokens=1))
    with pytest.raises(ValueError, match="encoder"):
        eng.step()
