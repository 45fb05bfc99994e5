"""Stateful decode Programs: the (prefill, decode) pair sharing
persistent compiler-owned KV-cache regions, the ProgramState carrier,
prefill+decode parity vs the legacy ``init_cache``/``decode_step``
loop, persistent-region lifetime invariants, the serving engine's
prefill-once/decode-per-tick path, the decode_attention dispatch, and
the windowed-attention rolling-KV plan (window-sized regions, ring
prefill conversion, occupancy-masked decode, slot-cache hygiene)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import init_params, transformer
from repro.runtime import executor

K0 = jax.random.PRNGKey(0)


def _cfg(name="smollm-360m", **over):
    cfg = REGISTRY[name].smoke()
    return dataclasses.replace(cfg, **over) if over else cfg


def _setup(cfg, slots=2, max_len=16):
    params = init_params(transformer.param_defs(cfg), K0)
    pair = transformer.compile_program_pair(cfg, slots=slots,
                                            max_len=max_len)
    state = executor.init_program_state(pair)
    return params, pair, state


def _prefill_slot(pair, params, state, slot, prompt, max_len, *,
                  impl="reference", interpret=None):
    padded = np.zeros((1, max_len), np.int32)
    padded[0, :len(prompt)] = prompt
    return executor.run_prefill(pair.prefill, params, jnp.asarray(padded),
                                state, slot, len(prompt), impl=impl,
                                interpret=interpret)


# --- prefill + N-decode parity vs the legacy cache loop ----------------------------
@pytest.mark.parametrize("name", ["smollm-360m", "llama3-8b"])
def test_prefill_and_decode_match_legacy_cache_loop(name):
    """Program prefill + N decode steps == teacher-forcing the same
    tokens through ``init_cache``/``decode_step``, logits <= 1e-5 at
    every step (both slots live, equal-length prompts so the legacy
    batch advances in lockstep)."""
    cfg = _cfg(name)
    slots, max_len, P, N = 2, 16, 5, 4
    params, pair, state = _setup(cfg, slots, max_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(slots, P)).astype(np.int32)

    # legacy oracle: feed every prompt token through the decode loop
    cache = transformer.init_cache(cfg, slots, max_len)
    for t in range(P):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(prompts[:, t]), cfg,
            impl="reference")

    for slot in range(slots):
        logits, state = _prefill_slot(pair, params, state, slot,
                                      prompts[slot], max_len)
        np.testing.assert_allclose(
            np.asarray(logits[0, P - 1]), np.asarray(leg_logits[slot]),
            rtol=0, atol=1e-5)
    assert list(np.asarray(state.lengths)) == [P] * slots

    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(N):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(np.asarray(dec_logits),
                                   np.asarray(leg_logits),
                                   rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    assert list(np.asarray(state.lengths)) == [P + N] * slots


def test_decode_rolls_cache_past_max_len():
    """Positions past max_len overwrite the oldest rows (the legacy
    rolling rule) — lengths keep counting, kv_len saturates, logits
    still match decode_step."""
    cfg = _cfg(n_layers=2)
    slots, max_len, P = 1, 8, 8
    params, pair, state = _setup(cfg, slots, max_len)
    prompt = np.arange(1, P + 1, dtype=np.int32)
    cache = transformer.init_cache(cfg, slots, max_len)
    for t in range(P):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(prompt[t:t + 1]), cfg,
            impl="reference")
    _, state = _prefill_slot(pair, params, state, 0, prompt, max_len)
    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(3):                     # cache full: rolling overwrite
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(np.asarray(dec_logits),
                                   np.asarray(leg_logits),
                                   rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)


@pytest.mark.pallas
def test_decode_pallas_interpret_parity():
    """The decode Program runs on the Pallas kernels (matmul +
    decode_attention) with the schedule's exact blocks."""
    cfg = _cfg(n_layers=1)
    params, pair, state = _setup(cfg, slots=1, max_len=16)
    prompt = np.asarray([3, 1, 4], np.int32)
    _, state = _prefill_slot(pair, params, state, 0, prompt, 16,
                             impl="pallas", interpret=True)
    ref_state = executor.init_program_state(pair)
    _, ref_state = _prefill_slot(pair, params, ref_state, 0, prompt, 16)
    toks = jnp.asarray([7], jnp.int32)
    out, _ = executor.run_decode(pair.decode, params, toks, state,
                                 impl="pallas", interpret=True)
    ref, _ = executor.run_decode(pair.decode, params, toks, ref_state,
                                 impl="reference")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# --- the lane-dense KV region layout ----------------------------------------------
def _row_major_pair(cfg, slots, max_len, monkeypatch):
    """The pair compiled with every KV region in the row-major
    (slots, rows, KV, hd) layout, the one a head_dim that does not fold
    keeps."""
    from repro.core import regions
    monkeypatch.setattr(regions, "kv_fold", lambda rows, head_dim: None)
    transformer._compile_program_pair.cache_clear()
    try:
        return transformer.compile_program_pair(cfg, slots=slots,
                                                max_len=max_len)
    finally:
        monkeypatch.undo()
        transformer._compile_program_pair.cache_clear()


@pytest.mark.parametrize("head_dim,fold,window", [
    (16, 8, None), (64, 2, None), (128, 1, None), (16, 4, 4)])
def test_folded_region_matches_row_major_layout(monkeypatch, head_dim,
                                                fold, window):
    """Whole prefill into slot 0, chunked prefill into slot 1, then
    decode ticks past max_len (slot 1's ring wraps) with slot 0 dead on
    one tick: the lane-dense regions, unfolded, are bitwise the
    row-major regions after every call, and every logit is bitwise
    equal.  The windowed case folds a 4-row ring into one row."""
    from repro.kernels.decode_attention import unfold_kv
    cfg = _cfg(n_layers=2, head_dim=head_dim, attn_window=window)
    slots, max_len, P0, P1, chunk = 2, 16, 5, 11, 4
    params, pair, state = _setup(cfg, slots, max_len)
    flat = _row_major_pair(cfg, slots, max_len, monkeypatch)
    flat_state = executor.init_program_state(flat)
    for op in pair.decode.ops:
        if op.kernel == "decode_attention":
            assert op.attn.kv_fold == fold
            assert flat.decode.op(op.name).attn.kv_fold is None
    assert f"fold={fold}" in pair.decode.listing()
    assert "fold=" not in flat.decode.listing()

    def check(a, b, sa, sb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for name, rid in pair.persistent.items():
            got = unfold_kv(sa.caches[rid], fold)      # (slots, KV, S, hd)
            want = sb.caches[flat.persistent[name]].transpose(0, 2, 1, 3)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want), err_msg=name)

    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab, size=n).astype(np.int32)
               for n in (P0, P1)]
    la, state = _prefill_slot(pair, params, state, 0, prompts[0], max_len)
    lb, flat_state = _prefill_slot(flat, params, flat_state, 0, prompts[0],
                                   max_len)
    check(la, lb, state, flat_state)
    padded = np.zeros((1, max_len), np.int32)
    padded[0, :P1] = prompts[1]
    for s0 in range(0, P1, chunk):
        args = [jnp.asarray(x, jnp.int32)
                for x in ([1], [s0], [min(s0 + chunk, P1)], [P1], [0])]
        la, state = executor.run_prefill_chunk(
            pair.prefill, params, jnp.asarray(padded), state, *args,
            impl="reference")
        lb, flat_state = executor.run_prefill_chunk(
            flat.prefill, params, jnp.asarray(padded), flat_state, *args,
            impl="reference")
        check(la, lb, state, flat_state)
    toks = jnp.asarray([3, 9], jnp.int32)
    for t in range(7):                     # slot 1: 11 + 7 > 16 rows
        mask = jnp.asarray([t != 2, True])
        la, state = executor.run_decode(pair.decode, params, toks, state,
                                        mask, impl="reference")
        lb, flat_state = executor.run_decode(flat.decode, params, toks,
                                             flat_state, mask,
                                             impl="reference")
        check(la, lb, state, flat_state)
        toks = jnp.argmax(la, axis=-1).astype(jnp.int32)
    assert list(np.asarray(state.lengths)) == [P0 + 6, P1 + 7]


def test_head_dim_112_keeps_row_major_layout():
    """A head_dim that neither divides nor is a multiple of 128 keeps
    the row-major (slots, rows, KV, hd) region and the transposing
    decode path; prefill + decode still match the legacy cache loop."""
    cfg = _cfg(n_layers=2, head_dim=112)
    slots, max_len, P = 2, 16, 5
    params, pair, state = _setup(cfg, slots, max_len)
    for r in pair.decode.plan.persistent_regions():
        assert r.fold is None
        assert r.shape == (slots, max_len, cfg.n_kv_heads, 112)
    assert pair.decode.op("l0.attn").attn.kv_fold is None
    assert "fold=" not in pair.decode.listing()
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(slots, P)).astype(np.int32)
    cache = transformer.init_cache(cfg, slots, max_len)
    for t in range(P):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(prompts[:, t]), cfg,
            impl="reference")
    for slot in range(slots):
        logits, state = _prefill_slot(pair, params, state, slot,
                                      prompts[slot], max_len)
        np.testing.assert_allclose(np.asarray(logits[0, P - 1]),
                                   np.asarray(leg_logits[slot]),
                                   rtol=0, atol=1e-5)
    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(3):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(np.asarray(dec_logits),
                                   np.asarray(leg_logits),
                                   rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)


# --- persistent-region lifetime ----------------------------------------------------
def test_persistent_regions_shared_and_never_reused():
    """The pair shares one persistent table: identical allocator-owned
    ids in both plans, disjoint from every transient region, never
    assigned to an op output, and sized (slots, KV, max_len / f, f * hd),
    lane-dense with f = 128 / hd recorded on the region."""
    cfg = _cfg()
    slots, max_len = 3, 16
    _, pair, _ = _setup(cfg, slots, max_len)
    pre, dec = pair.prefill.plan, pair.decode.plan
    assert pre.persistent == dec.persistent == pair.persistent
    assert len(pair.persistent) == 2 * cfg.n_layers
    for plan in (pre, dec):
        transient = {r.rid for r in plan.regions
                     if r.kind != "persistent"}
        persistent = set(plan.persistent.values())
        assert not transient & persistent
        # ping-pong/pinned reuse never hands out a persistent id
        assert not set(plan.out_region.values()) & persistent
        for name, rid in plan.persistent.items():
            r = plan.region(rid)
            assert r.kind == "persistent" and r.name == name
            f = 128 // cfg.hd
            assert r.fold == f
            assert r.shape == (slots, cfg.n_kv_heads, max_len // f,
                               f * cfg.hd)
    # the transient footprint still matches the stateless lowering
    flat = transformer.compile_program(cfg, batch=1, seq=max_len)
    assert dec.n_pingpong == flat.plan.n_pingpong
    assert dec.n_pinned == flat.plan.n_pinned


def test_program_ops_carry_cache_regions_and_decode_blocks():
    """Prefill flash ops write the cache; decode ops read/write it with
    the decode-regime block choice from select_attention_blocks."""
    from repro.core.hw import TPU_V5E
    from repro.core.tiling import select_attention_blocks
    cfg = _cfg()
    max_len = 16
    _, pair, _ = _setup(cfg, slots=2, max_len=max_len)
    want = select_attention_blocks(1, max_len, cfg.hd, 4, TPU_V5E)
    for i in range(cfg.n_layers):
        pre_op = pair.prefill.op(f"l{i}.attn")
        dec_op = pair.decode.op(f"l{i}.attn")
        assert pre_op.kernel == "flash_attention"
        assert dec_op.kernel == "decode_attention"
        assert (pre_op.k_cache_region == dec_op.k_cache_region
                == pair.persistent[f"l{i}.k_cache"])
        assert (pre_op.v_cache_region == dec_op.v_cache_region
                == pair.persistent[f"l{i}.v_cache"])
        assert (dec_op.attn.block_q, dec_op.attn.block_kv) == want
    listing = pair.listing()
    assert "persistent KV regions" in listing
    assert "decode_attention" in listing and "cache=" in listing


def test_stateless_run_rejects_decode_program():
    cfg = _cfg(n_layers=1)
    params, pair, _ = _setup(cfg, slots=1, max_len=8)
    with pytest.raises(ValueError, match="ProgramState"):
        executor.run(pair.decode, params, jnp.zeros((1,), jnp.int32),
                     impl="reference")


# --- windowed attention: rolling KV regions as a region-plan decision --------------
def test_windowed_region_plan_shrinks_kv_to_window():
    """A sliding window sizes every persistent KV region at
    min(max_len, attn_window) rows per slot — persistent bytes shrink
    by exactly max_len/W vs the full plan, transient plan unchanged."""
    slots, max_len, W = 2, 16, 4
    full = transformer.compile_program_pair(_cfg(), slots=slots,
                                            max_len=max_len)
    pair = transformer.compile_program_pair(_cfg(attn_window=W),
                                            slots=slots, max_len=max_len)
    cfg = _cfg(attn_window=W)
    for plan in (pair.prefill.plan, pair.decode.plan):
        for r in plan.persistent_regions():
            # a 4-row region folds its 4 rows into one 64-lane row
            assert r.fold == W
            assert r.shape == (slots, cfg.n_kv_heads, 1, W * cfg.hd)
    assert pair.persistent_bytes * (max_len // W) == full.persistent_bytes
    assert pair.decode.plan.n_pingpong == full.decode.plan.n_pingpong
    assert pair.decode.plan.n_pinned == full.decode.plan.n_pinned
    # the decode ops carry the window and a window-capped block_kv
    from repro.core.hw import TPU_V5E
    from repro.core.tiling import select_attention_blocks
    want = select_attention_blocks(1, W, cfg.hd, 4, TPU_V5E, window=W)
    for i in range(cfg.n_layers):
        op = pair.decode.op(f"l{i}.attn")
        assert op.attn.window == W
        assert (op.attn.block_q, op.attn.block_kv) == want
    assert f"win={W}" in pair.decode.listing()
    assert f"fold={W}" in pair.decode.listing()


def test_windowed_prefill_and_decode_match_legacy_past_max_len():
    """Windowed parity: prompt longer than the window, decode past
    max_len — the ring-converted prefill cache plus rolling decode
    matches the legacy init_cache/decode_step loop <= 1e-5 at every
    step (kv_cache_len rows resident, never max_len)."""
    cfg = _cfg(n_layers=2, attn_window=4)
    slots, max_len, P, N = 2, 8, 6, 8          # P > W; P + N > max_len
    params, pair, state = _setup(cfg, slots, max_len)
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(slots, P)).astype(np.int32)

    cache = transformer.init_cache(cfg, slots, max_len)
    assert cache["k"].shape[3] == 4            # legacy ring is window-sized
    for t in range(P):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(prompts[:, t]), cfg,
            impl="reference")

    for slot in range(slots):
        logits, state = _prefill_slot(pair, params, state, slot,
                                      prompts[slot], max_len)
        np.testing.assert_allclose(
            np.asarray(logits[0, P - 1]), np.asarray(leg_logits[slot]),
            rtol=0, atol=1e-5)

    toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    for _ in range(N):
        leg_logits, cache = transformer.decode_step(
            params, cache, jnp.asarray(toks), cfg, impl="reference")
        dec_logits, state = executor.run_decode(
            pair.decode, params, jnp.asarray(toks), state,
            impl="reference")
        np.testing.assert_allclose(np.asarray(dec_logits),
                                   np.asarray(leg_logits),
                                   rtol=0, atol=1e-5)
        toks = np.argmax(np.asarray(leg_logits), axis=-1).astype(np.int32)
    assert list(np.asarray(state.lengths)) == [P + N] * slots


def test_decode_mask_keeps_dead_slots_inert():
    """Unoccupied slots under the occupancy mask neither advance their
    length nor write cache rows — the live slot's logits are identical
    to a fully-live run."""
    cfg = _cfg(n_layers=1, attn_window=4)
    params, pair, state = _setup(cfg, slots=2, max_len=8)
    _, state = _prefill_slot(pair, params, state, 0, [3, 1, 4], 8)
    before = {rid: np.asarray(buf) for rid, buf in state.caches.items()}
    toks = jnp.asarray([7, 9], jnp.int32)
    mask = jnp.asarray([True, False])
    logits, new_state = executor.run_decode(pair.decode, params, toks,
                                            state, mask, impl="reference")
    assert list(np.asarray(new_state.lengths)) == [4, 0]   # only slot 0
    for rid, buf in new_state.caches.items():
        np.testing.assert_array_equal(np.asarray(buf)[1], before[rid][1])
    full_logits, _ = executor.run_decode(pair.decode, params, toks, state,
                                         impl="reference")
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full_logits[0]), rtol=0, atol=0)


def test_windowed_dead_slot_readmission_has_no_stale_rows():
    """Admit -> retire -> re-admit on a windowed pair: the re-admitted
    request attends no stale rows from the dead period (its tokens
    match a fresh single-request engine), even though the rolling
    prefill does not rewrite a full max_len row region."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg(n_layers=2, attn_window=4)
    params = init_params(transformer.param_defs(cfg), K0)
    max_len, max_new = 8, 5

    def serve(reqs):
        eng = ServingEngine(cfg, params, slots=1, max_len=max_len,
                            impl="reference", use_program=True)
        assert eng._lm_program
        for r in reqs:
            eng.submit(r)
        done = eng.run_until_drained()
        assert eng.n_prefill_recomputes == 0
        return sorted(done, key=lambda r: r.uid)

    # slot 0 serves A to completion (its ring fills with A's rows),
    # then B is admitted into the same slot
    a = Request(uid=0, prompt=np.asarray([9, 8, 7, 6, 5, 4], np.int32),
                max_new_tokens=max_new)
    b = Request(uid=1, prompt=np.asarray([2, 7], np.int32),
                max_new_tokens=max_new)
    reused = serve([a, b])
    fresh = serve([Request(uid=1, prompt=np.asarray([2, 7], np.int32),
                           max_new_tokens=max_new)])
    assert reused[1].out_tokens == fresh[0].out_tokens


def test_lm_admit_reuses_slot_freed_same_tick():
    """A slot freed during admission (max_new_tokens=1 retires on the
    prefill token) admits the next queued request in the same tick
    instead of idling until the next one."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg(n_layers=1)
    params = init_params(transformer.param_defs(cfg), K0)
    eng = ServingEngine(cfg, params, slots=1, max_len=8,
                        impl="reference", use_program=True)
    for i in range(2):
        eng.submit(Request(uid=i, prompt=np.asarray([5, 6], np.int32),
                           max_new_tokens=1))
    finished = eng.step()
    assert len(finished) == 2 and not eng.queue
    assert eng.n_prefills == 2 and eng.n_prefill_recomputes == 0


def test_unlowerable_family_warns_with_full_blocker_list():
    """Fallback to the legacy loop names *every* blocker (vlm: family,
    gated cross-attention, vision inputs), never a generic 'not
    lowered' or just the first hit — and the engine records the full
    list for callers that require the program path."""
    from repro.serving import ServingEngine
    cfg = REGISTRY["llama-3.2-vision-11b"].smoke()
    params = init_params(transformer.param_defs(cfg), K0)
    with pytest.warns(RuntimeWarning, match="family=vlm"):
        eng = ServingEngine(cfg, params, slots=1, max_len=8,
                            impl="reference", use_program=True)
    assert not eng._lm_program
    for blocker in ("family=vlm", "cross-attention", "vision-encoder"):
        assert blocker in eng.fallback_reason


def test_serve_program_exits_nonzero_on_fallback():
    """launch/serve.py --program refuses to silently serve an
    explicitly-requested program path through the legacy loop."""
    from repro.launch import serve
    with pytest.warns(RuntimeWarning), pytest.raises(SystemExit) as ei:
        serve.main(["--arch", "llama-3.2-vision-11b", "--smoke",
                    "--program", "--slots", "1", "--max-len", "8",
                    "--requests", "0"])
    assert ei.value.code == 2


def test_engine_rejects_plain_lm_program():
    """A bare stateless Program (the retired recompute API) is refused
    with a pointer to compile_program_pair, not an opaque crash."""
    from repro.serving import ServingEngine
    cfg = _cfg(n_layers=1)
    params = init_params(transformer.param_defs(cfg), K0)
    flat = transformer.compile_program(cfg, batch=1, seq=8)
    with pytest.raises(TypeError, match="compile_program_pair"):
        ServingEngine(cfg, params, slots=1, max_len=8, program=flat)
    # and a pair compiled for other serving geometry is caught up front
    pair = transformer.compile_program_pair(cfg, slots=2, max_len=8)
    with pytest.raises(ValueError, match="slots/max_len"):
        ServingEngine(cfg, params, slots=4, max_len=8, program=pair)
    # ...including a windowed max_len mismatch, which the persistent
    # region shapes alone cannot see (rows collapse to the window)
    wcfg = _cfg(n_layers=1, attn_window=4)
    wparams = init_params(transformer.param_defs(wcfg), K0)
    wpair = transformer.compile_program_pair(wcfg, slots=1, max_len=16)
    with pytest.raises(ValueError, match="slots/max_len"):
        ServingEngine(wcfg, wparams, slots=1, max_len=8, program=wpair)
    # ...and a pair whose window disagrees with the engine's config
    # (same recorded slots/max_len, different region rows)
    cfg1 = _cfg(n_layers=1)
    params1 = init_params(transformer.param_defs(cfg1), K0)
    wpair8 = transformer.compile_program_pair(wcfg, slots=1, max_len=8)
    with pytest.raises(ValueError, match="slots/max_len"):
        ServingEngine(cfg1, params1, slots=1, max_len=8, program=wpair8)


# --- serving round trip ------------------------------------------------------------
def test_serving_stateful_round_trip_matches_decode_oracle():
    """Engine tokens == greedy generation through the legacy
    ``init_cache``/``decode_step`` loop, per request — and the engine
    never recomputes a prefill."""
    from repro.serving import Request, ServingEngine
    cfg = _cfg(n_layers=2)
    params = init_params(transformer.param_defs(cfg), K0)
    max_len, max_new = 16, 4
    eng = ServingEngine(cfg, params, slots=2, max_len=max_len,
                        impl="reference", use_program=True)
    assert eng.program is not None
    prompts = [[3, 1, 4], [15]]
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=np.asarray(p, np.int32),
                           max_new_tokens=max_new))
    done = sorted(eng.run_until_drained(), key=lambda r: r.uid)
    assert len(done) == 2 and all(r.done for r in done)
    assert eng.n_prefills == 2
    assert eng.n_prefill_recomputes == 0
    for req, prompt in zip(done, prompts):
        cache = transformer.init_cache(cfg, 1, max_len)
        want, logits = [], None
        for t in prompt:
            logits, cache = transformer.decode_step(
                params, cache, jnp.asarray([t], jnp.int32), cfg,
                impl="reference")
        for _ in range(max_new):
            nxt = int(np.argmax(np.asarray(logits)[0]))
            want.append(nxt)
            logits, cache = transformer.decode_step(
                params, cache, jnp.asarray([nxt], jnp.int32), cfg,
                impl="reference")
        assert req.out_tokens == want


def test_serving_decode_dispatches_decode_attention(monkeypatch):
    """Decode ticks run the decode_attention kernel — never the causal
    flash recompute.  The engine's runners are jitted, so the spies see
    each program's *trace*: flash appears exactly once (the prefill
    trace), decode_attention in the decode trace, and multiple decode
    ticks replay the compiled decode executable (no flash anywhere)."""
    from repro.serving import Request, ServingEngine
    # Fresh depth so the lru-cached pair (and its jitted runners) from
    # other tests cannot satisfy this engine with a stale trace.
    cfg = _cfg(n_layers=3)
    params = init_params(transformer.param_defs(cfg), K0)
    decode_calls, flash_calls = [], []
    real_decode = executor.decode_attention
    real_flash = executor.flash_attention

    def spy_decode(q, k, v, **kw):
        decode_calls.append((q.shape, k.shape, kw.get("block_kv"),
                             kw.get("fold")))
        return real_decode(q, k, v, **kw)

    def spy_flash(q, k, v, **kw):
        flash_calls.append(q.shape)
        return real_flash(q, k, v, **kw)

    monkeypatch.setattr(executor, "decode_attention", spy_decode)
    monkeypatch.setattr(executor, "flash_attention", spy_flash)
    eng = ServingEngine(cfg, params, slots=2, max_len=16,
                        impl="reference", use_program=True)
    eng.submit(Request(uid=0, prompt=np.asarray([5, 6], np.int32),
                       max_new_tokens=3))
    eng.run_until_drained()
    assert eng.n_decode_ticks >= 2
    # flash traced only by the prefill program; the decode trace holds
    # decode_attention ops exclusively
    assert len(flash_calls) == cfg.n_layers
    assert len(decode_calls) == cfg.n_layers
    qshape, kshape, bkv, fold = decode_calls[0]
    assert qshape == (2, cfg.n_heads, cfg.hd)
    # the lane-dense region as it rests: 16 rows folded 8 to a row
    assert fold == 8
    assert kshape == (2, cfg.n_kv_heads, 16 // fold, fold * cfg.hd)
    pair = transformer.compile_program_pair(cfg, slots=2, max_len=16)
    assert bkv == pair.decode.op("l0.attn").attn.block_kv


def test_program_state_is_donatable_pytree():
    """ProgramState round-trips through tree flatten/unflatten and the
    jitted decode runner keeps buffer shapes/dtypes stable (the
    donation contract)."""
    cfg = _cfg(n_layers=1)
    params, pair, state = _setup(cfg, slots=2, max_len=8)
    leaves, treedef = jax.tree.flatten(state)
    rebuilt = jax.tree.unflatten(treedef, leaves)
    assert sorted(rebuilt.caches) == sorted(state.caches)
    fn = executor.jitted_decode_runner(pair.decode, impl="reference")
    logits, new_state = fn(params, jnp.zeros((2,), jnp.int32), state)
    assert logits.shape == (2, cfg.vocab)
    for rid, buf in new_state.caches.items():
        assert buf.shape == state.caches[rid].shape
        assert buf.dtype == state.caches[rid].dtype
    assert list(np.asarray(new_state.lengths)) == [1, 1]
