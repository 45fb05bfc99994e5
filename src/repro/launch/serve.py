"""Production serving driver: continuous-batched decode.

    PYTHONPATH=src python -m repro.launch.serve \
        --arch smollm-360m --smoke --requests 8 --max-new 16

CNN archs (alexnet-owt / resnet18 / resnet50) serve image-classify
requests through the compiled-Program fast path:

    PYTHONPATH=src python -m repro.launch.serve --arch alexnet-owt \
        --slots 2 --requests 4

Dense LM archs (smollm-360m / llama3-8b class) serve token requests
statefully through the compiled (prefill, decode) Program pair — each
request is prefilled exactly once into a persistent compiler-owned
KV-cache region, then every tick runs the decode Program (O(1) in
prompt length; the engine's ``n_prefill_recomputes`` counter stays 0):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --program --requests 4 --max-new 8

``--paged`` swaps in the paged §5.1 region plan (KV page pools + page
table, copy-on-write prefix sharing, optional ``--kv-quant int8``
pages); ``--shared-prefix N`` makes every prompt open with the same N
tokens so admission actually shares pages:

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --program --paged --shared-prefix 32 --requests 4

``--chunk-size N`` splits each prefill into N-row chunks scheduled one
per decode tick (long prompts stop stalling in-flight streams — the
engine's ``n_starved_ticks`` stays 0); ``--spec-decode K`` turns on
greedy speculative decoding, with ``--draft ARCH`` naming a separate
draft model (default: self-draft):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
        --smoke --program --chunk-size 8 --spec-decode 3 --requests 4
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time

import jax
import numpy as np

from ..configs import CNN_REGISTRY, get_config
from ..models import get_model, init_params
from ..obs import Observability
from ..serving import Request, ServingEngine
from .compile_cache import use_compile_cache


def _build_obs(args) -> Observability:
    return Observability(flight_path=args.flight_out)


def _write_artifacts(args, obs: Observability) -> None:
    """Serialize the metrics plane: a JSON snapshot at --metrics-out
    plus the Prometheus text exposition next to it (``.prom``), and
    flush/close the flight recorder.  Called on *every* exit path —
    including the --program exit-code-2 fallback, so a failed run is
    diagnosable from its artifacts."""
    obs.close()
    if not args.metrics_out:
        return
    with open(args.metrics_out, "w") as f:
        f.write(obs.registry.to_json(arch=args.arch, argv=sys.argv[1:]))
    prom = args.metrics_out + ".prom"
    with open(prom, "w") as f:
        f.write(obs.registry.prometheus_text())
    print(f"metrics snapshot -> {args.metrics_out} (+ {prom})")
    if args.flight_out:
        print(f"flight record -> {args.flight_out}")


def _drain(eng, args) -> list:
    """run_until_drained with the periodic console dashboard: every
    --dash-every ticks one line of engine vitals, read off the same
    registry the artifacts serialize."""
    if not args.dash_every:
        return eng.run_until_drained()
    done = []
    for _ in range(10_000):
        done += eng.step()
        if eng._tick_no % args.dash_every == 0:
            print(eng.dashboard_line())
        if (not eng.live and not eng.queue and not eng.admission
                and not eng._prefilling):
            break
    return done


def _serve_cnn(args) -> None:
    """Image-classification serving: the engine executes the compiled
    Program (schedule -> regions -> instruction stream) per tick."""
    from ..models import cnn
    cfg = CNN_REGISTRY[args.arch]
    params = init_params(cnn.param_defs(cfg), jax.random.PRNGKey(0))
    if args.ckpt:
        from ..checkpoint import restore_checkpoint
        (params, _), step = restore_checkpoint(args.ckpt, (params, {}))
        print(f"restored params from step {step}")
    obs = _build_obs(args)
    eng = ServingEngine(cfg, params, slots=args.slots, obs=obs)
    print(eng.program.listing())
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    for i in range(args.requests):
        img = rng.standard_normal(
            (cfg.input_hw, cfg.input_hw, cfg.input_ch)).astype(np.float32)
        eng.submit(Request(uid=i, prompt=img))
    done = eng.run_until_drained()
    dt = time.perf_counter() - t0
    print(f"served {len(done)} images in {dt:.2f}s "
          f"({len(done) / dt:.1f} img/s)")
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: class {r.out_tokens[0]}")
    _write_artifacts(args, obs)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir to load params from")
    ap.add_argument("--program", action="store_true",
                    help="serve LM tokens through the compiled Program "
                         "(every registered state family: dense/MoE, "
                         "windowed, hybrid SSM, rwkv, whisper; exits "
                         "non-zero if the config cannot lower — "
                         "no silent legacy fallback when the program "
                         "path was explicitly requested)")
    ap.add_argument("--window", type=int, default=None,
                    help="override attn_window (sliding-window "
                         "attention); the program path then sizes the "
                         "persistent KV regions to min(max_len, window)")
    ap.add_argument("--paged", action="store_true",
                    help="compile the paged §5.1 region plan: KV page "
                         "pools + per-slot page table, host-side page "
                         "allocator with copy-on-write prefix sharing "
                         "(requires --program)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="rows per KV page (must divide --max-len)")
    ap.add_argument("--kv-quant", choices=["int8"], default=None,
                    help="quantize paged KV pages to int8 with "
                         "per-page scales (~2x resident cache bytes)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend this many identical tokens to every "
                         "prompt (exercises paged copy-on-write prefix "
                         "sharing; CI asserts shared pages > 0)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="chunked prefill: admit prompts one N-row "
                         "chunk per decode tick instead of a whole "
                         "prefill at admission (bounds per-tick "
                         "latency; requires --program)")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decode: a draft Program pair "
                         "proposes K tokens per tick, the target "
                         "verifies the burst in one batched step "
                         "(greedy only; requires --program)")
    ap.add_argument("--draft", default=None,
                    help="draft arch for --spec-decode (same vocab; "
                         "default: self-draft with the target weights)")
    ap.add_argument("--long-prompt", type=int, default=0,
                    help="inject one prompt of this length two ticks "
                         "into the run (the mid-stream long-prompt "
                         "scenario the chunked-prefill CI smoke pins)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the final metrics-registry snapshot as "
                         "JSON to PATH and the Prometheus text "
                         "exposition to PATH.prom (written on every "
                         "exit path, including --program fallback)")
    ap.add_argument("--flight-out", default=None, metavar="PATH",
                    help="record the JSONL flight record (typed "
                         "per-request lifecycle events + per-tick "
                         "snapshots) to PATH; replay offline with "
                         "repro.obs.replay_summary")
    ap.add_argument("--dash-every", type=int, default=0, metavar="N",
                    help="print a one-line console dashboard every N "
                         "engine ticks; 0 = off")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.paged and not args.program:
        print("error: --paged requires --program (the paged plan only "
              "exists on the stateful Program path)", file=sys.stderr)
        raise SystemExit(2)

    if args.arch in CNN_REGISTRY:
        _serve_cnn(args)
        return

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.window:
        cfg = dataclasses.replace(cfg, attn_window=args.window)
    api = get_model(cfg)
    params = init_params(api.param_defs(cfg), jax.random.PRNGKey(0))
    if args.ckpt:
        from ..checkpoint import restore_checkpoint
        (params, _), step = restore_checkpoint(args.ckpt, (params, {}))
        print(f"restored params from step {step}")

    draft_cfg = draft_params = None
    if args.draft:
        draft_cfg = get_config(args.draft)
        if args.smoke:
            draft_cfg = draft_cfg.smoke()
        draft_params = init_params(
            get_model(draft_cfg).param_defs(draft_cfg),
            jax.random.PRNGKey(1))

    # The engine compiles the (prefill, decode) Program pair itself and
    # warns (once, at construction) when a family has no lowering.
    obs = _build_obs(args)
    eng = ServingEngine(cfg, params, slots=args.slots,
                        max_len=args.max_len, use_program=args.program,
                        paged=args.paged, page_size=args.page_size,
                        kv_quant=args.kv_quant,
                        chunk_size=args.chunk_size,
                        spec_k=args.spec_decode, draft_cfg=draft_cfg,
                        draft_params=draft_params, obs=obs)
    if args.program and not eng.on_program_path:
        # The user *asked* for the program path; a silent legacy-loop
        # fallback would misreport what was measured.  The engine's
        # fallback_reason names the specific blocker — and the metrics
        # / flight artifacts carry the structured twin (the fallback
        # event + serving_fallback{fallback_reason} gauge).
        _write_artifacts(args, obs)
        print(f"error: --program requested but {cfg.name} has no "
              f"decode-Program lowering "
              f"({eng.fallback_reason or 'unknown reason'})",
              file=sys.stderr)
        raise SystemExit(2)
    if eng.program is not None:
        print(eng.program.listing().splitlines()[0])
    rng = np.random.default_rng(0)

    def _extra():
        # Families with a side-channel input (audio: stub encoder
        # frames) get one per request; admission encodes it into the
        # slot's read-only persistent memory regions.
        if api.extra_input != "encoder_frames":
            return None
        return rng.standard_normal(
            (cfg.encoder_seq, cfg.d_model)).astype(np.float32)

    t0 = time.perf_counter()
    prefix = rng.integers(0, cfg.vocab,
                          size=args.shared_prefix).astype(np.int32)
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab,
                              size=rng.integers(1, 8)).astype(np.int32)
        if args.shared_prefix:
            prompt = np.concatenate([prefix, prompt])
        eng.submit(Request(uid=i, prompt=prompt,
                           max_new_tokens=args.max_new, extra=_extra()))
    done = []
    if args.long_prompt:
        # Two ticks of steady decode, then the long prompt lands
        # mid-stream — with --chunk-size its prefill interleaves with
        # the in-flight streams instead of stalling them.
        for _ in range(2):
            done += eng.step()
        eng.submit(Request(
            uid=args.requests,
            prompt=rng.integers(0, cfg.vocab,
                                size=args.long_prompt).astype(np.int32),
            max_new_tokens=args.max_new, extra=_extra()))
    done += _drain(eng, args)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens "
          f"in {dt:.2f}s ({total_tokens/dt:.1f} tok/s)")
    if eng.on_program_path:
        print(f"prefills={eng.n_prefills} "
              f"prefill_recomputes={eng.n_prefill_recomputes} "
              f"decode_ticks={eng.n_decode_ticks}")
        if eng.chunk_size is not None:
            print(f"prefill_chunks={eng.n_prefill_chunks} "
                  f"starved_ticks={eng.n_starved_ticks}")
        if eng.spec_k:
            print(f"spec_proposed={eng.n_spec_proposed} "
                  f"spec_accepted={eng.n_spec_accepted} "
                  f"spec_rollbacks={eng.n_spec_rollbacks}")
        if eng.admission.n_rejected or eng.admission.n_requeued:
            print(f"rejected={eng.admission.n_rejected} "
                  f"requeued={eng.admission.n_requeued} "
                  f"last_blocked={eng.admission.last_blocked}")
    if args.paged:
        print(f"shared_pages={eng.n_shared_pages} "
              f"cow_forks={eng.n_cow_forks} "
              f"pool_used={eng._pool.used_pages} "
              f"pool_free={eng._pool.free_pages}")
    for r in sorted(done, key=lambda r: r.uid)[:4]:
        print(f"  req {r.uid}: {list(r.prompt)} -> {r.out_tokens}")
    _write_artifacts(args, obs)


if __name__ == "__main__":
    main()
