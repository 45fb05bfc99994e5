"""Batched serving engine: continuous batching over a fixed-slot cache.

One prefill step admits a request into a free slot (its KV/state cache
written at that slot); every decode step advances all live slots by one
token.  Slots whose sequence emits EOS (or hits max_len) are freed and
refilled from the queue — the standard continuous-batching loop, sized
so the decode step is always full-batch (the bandwidth-bound regime the
decode_32k / long_500k cells measure).

Per-slot positions come from the models' per-sequence ``pos`` vector,
so mixed-progress batches are exact (verified in tests against
single-request decoding).

CNN workloads take the **program fast path**: a ``CNNConfig`` (or an
explicit ``program=``) makes the engine stateless — each tick batches
up to ``slots`` queued image requests and executes the compiled
``core/program.py::Program`` once through ``runtime/executor.py``, so
the compiler's schedule is what serves the traffic.

Dense-LM workloads are served **statefully** (``use_program=True``):
the engine compiles the (prefill, decode) Program pair
(``models/transformer.py::compile_program_pair``) whose persistent
KV-cache regions are owned by the §5.1 allocator, and keeps one
``runtime/executor.py::ProgramState`` across ticks.  Admission runs
the prefill Program once per request — full causal forward, cache
written at the admitted slot, first token emitted from the prompt's
last position — and every subsequent tick runs the decode Program: one
token per live slot against the cache, O(1) in prompt length.  Nothing
is ever prefilled twice (``n_prefill_recomputes`` stays 0 by
construction).  Windowed-attention configs serve on the same path with
persistent KV regions sized to the window (``min(max_len,
attn_window)`` rows per slot, rolling eviction-by-overwrite — the
§5.1 plan shrinks resident state by max_len/window).  ``paged=True``
compiles the third region scheme — the paged plan: fixed-size page
pools plus a per-slot page table, with admission, copy-on-write prefix
sharing, and on-demand page allocation decided host-side by a
``runtime/executor.py::PagePool`` between jitted calls
(``n_shared_pages`` / ``n_cow_forks`` count the wins; ``kv_quant=
"int8"`` additionally halves resident page bytes).

Persistent state is *generic named state*, not KV rows: each family's
``regions.state_specs`` hook mints its own per-slot specs — recurrent
SSM/conv state (hybrid, O(1) in sequence length), wkv matrices +
token-shift rows (rwkv), read-only encoder memory written at admission
by ``ModelApi.encode_memory`` (whisper) — and a ``StateCaps`` record
that gates the serving features per family: ``paged``/COW needs
KV-row-granular state, ``chunkable`` prefill needs resumable state
(``pair.chunk_blocker``), ``speculatable`` needs rollback-by-length-
truncation.  The engine consults the caps instead of assuming every
family is KV-shaped.  Families without a lowering (vlm) fall back to
the legacy ``decode_step`` loop with a single warning at engine
construction naming the *full* blocker list (``fallback_reason``).

The tick loop itself is throughput-grade (see docs/ARCHITECTURE.md,
"Serving loop"):

* **Chunked prefill** (``chunk_size``): admission only assigns the
  slot; the prompt prefills ``chunk_size`` rows per tick through one
  batched ``run_prefill_chunk`` call shared by every in-flight
  admission, bitwise-equal to a whole prefill.  Decode-first fairness
  bounds per-tick latency: live slots always advance
  (``n_starved_ticks`` stays 0), and a prefill completes within
  ``ceil(length/chunk_size)`` ticks of slot assignment.
* **Async admission with typed backpressure**: ``submit`` goes through
  a bounded ``serving/admission.py::AdmissionQueue`` — ``queue_full``
  rejects at the door with a ticket; ``no_free_slot`` /
  ``pages_exhausted`` stalls are recorded, and a pool-starved request
  is requeued at the *head* so later arrivals can never overtake it.
* **Speculative decode** (``spec_k``): a draft (prefill, decode) pair
  — ``compile_program_pair`` verbatim via ``compile_draft_pair`` —
  proposes up to k greedy tokens per tick; the target verifies the
  burst in one batched chunk call, accepts the longest agreeing
  prefix, emits the correcting token, and rolls back by truncating
  both states' lengths.  Greedy output is token-identical to
  speculation off (``n_spec_proposed`` / ``n_spec_accepted`` /
  ``n_spec_rollbacks`` count the wins next to the prefill metrics).

**Observability** (``obs=``, docs Stage 8): every engine reports
through one ``obs.Observability`` bundle — the ``n_*`` counters above
live on its ``MetricsRegistry`` (the attributes are read-through
properties), per-tick wallclock and TTFT / inter-token latency land in
fixed-bucket histograms (``tick_ms`` / ``ttft_ms`` / ``itl_ms``), and
when a flight recorder is attached every request's lifecycle — enqueue
→ admission ticket → prefill chunks → first token → per-token → spec
accept/rollback → COW fork → release — plus a per-tick engine snapshot
streams to JSONL (``obs.flight.replay_summary`` reconstructs the token
streams exactly).  The default bundle is counters-only: no recorder,
no extra device syncs — a bare engine pays a few float adds per tick
for its metrics plane.

**Phases** (``obs.span``): ``serving.step`` covers a tick and its
children tile it — on the CNN path ``serving.stage`` (batch, stack,
pad, transfer), ``serving.dispatch``, ``serving.wait`` (the logits'
``block_until_ready``), ``serving.fetch`` (to numpy) and
``serving.sample`` (argmax, retire); on the LM Program path
``serving.admit`` (one ``serving.prefill`` per admitted request inside
it), ``serving.chunk`` (chunked prefill), ``serving.pages`` (paged
plans), then ``serving.dispatch`` / ``serving.wait`` /
``serving.fetch`` / ``serving.sample`` for the decode tick, or
``serving.spec`` for a speculative one.  ``serving.submit`` covers
``submit``.  Each phase's seconds and count are counters on the
registry (``serving_phase_seconds_total{phase=...}``), and while a
profiler runs each span is a host annotation on the device trace's
clock.
"""
from __future__ import annotations

import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig, CNNConfig
from ..models import get_model
from ..obs import Observability
from . import admission as adm
from .admission import AdmissionQueue, AdmissionTicket

__all__ = ["Request", "ServingEngine"]


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (len,) int32 tokens, or (H, W, C) image
    max_new_tokens: int = 16
    # Family side-channel input (ModelApi.extra_input): encoder frames
    # for audio configs — admission runs ``encode_memory`` over it and
    # writes the result into the slot's read-only persistent regions.
    extra: np.ndarray | None = None
    out_tokens: list = field(default_factory=list)
    done: bool = False


@dataclass
class _InFlightPrefill:
    """A chunked admission mid-prefill: the slot is reserved (neither
    free nor live) while ``done`` walks the prompt in ``chunk_size``
    steps; ``admitted_tick`` dates the slot assignment so the
    completes-within-``ceil(length/chunk)``-ticks bound is checkable."""
    req: Request
    tokens: np.ndarray               # (max_len,) right-padded prompt window
    length: int                      # prompt rows to prefill
    done: int                        # rows already in the cache
    write_from: int                  # paged shared-prefix redirect
    admitted_tick: int


class ServingEngine:
    def __init__(self, cfg, params, *, slots: int = 8,
                 max_len: int = 256, eos_id: int | None = None,
                 impl: str = "auto", greedy: bool = True, program=None,
                 use_program: bool = False, paged: bool = False,
                 page_size: int = 16, page_pool: int | None = None,
                 kv_quant: str | None = None,
                 chunk_size: int | None = None,
                 queue_capacity: int | None = None,
                 spec_k: int = 0, draft_cfg=None, draft_params=None,
                 obs: Observability | None = None):
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos = eos_id
        self.impl = impl
        self.greedy = greedy
        self.live: dict[int, Request] = {}       # slot -> request
        self.queue: list[Request] = []           # legacy/CNN paths only
        # One metrics plane + flight recorder per engine (Stage 8).
        # The default bundle is counters-only (no recorder); callers
        # that want the flight record / Prometheus snapshot pass their
        # own bundle.
        self.obs = obs if obs is not None else Observability()
        self._init_metrics()
        # LM-program requests enter through the bounded admission queue
        # (typed backpressure, head requeue); ``submit`` routes there.
        # It shares the engine's registry: admission_* and serving_*
        # metrics land in one snapshot.
        self.admission = AdmissionQueue(queue_capacity,
                                        registry=self.obs.registry)
        self.chunk_size = chunk_size
        self.spec_k = spec_k
        self._spec = False
        self._prefilling: dict[int, _InFlightPrefill] = {}
        self._lm_program = False
        self._tick_no = 0
        # Why an LM config requested on the program path fell back to
        # the legacy decode loop (None = no fallback happened); callers
        # that *require* the program path (launch/serve.py --program)
        # check this instead of re-parsing the warning.
        self.fallback_reason: str | None = None
        self._pool = None                 # runtime/executor.py::PagePool
        self._slot_prompts: dict[int, tuple] = {}   # donor registry
        self._slot_len: dict[int, int] = {}         # host length mirror
        self._memory_writer = None        # ModelApi.encode_memory
        self._memory_input = None         # ModelApi.extra_input
        lm = isinstance(cfg, ArchConfig)
        if (program is not None or use_program) and lm:
            # Stateful LM program path: (prefill, decode) Program pair
            # sharing persistent KV regions + one ProgramState.
            from ..core.program import ProgramPair
            from ..models.transformer import compile_program_pair
            from ..runtime import executor
            if program is not None and not isinstance(program, ProgramPair):
                raise TypeError(
                    f"LM serving is stateful: pass the (prefill, decode) "
                    f"ProgramPair from models/transformer.py::"
                    f"compile_program_pair, got {type(program).__name__} "
                    f"(the per-tick prefill-recompute path was removed)")
            if program is not None:
                # Catch a geometry mismatch at construction, not as a
                # shape error mid-serve.  The pair records its compiled
                # (slots, max_len); the persistent-region shapes alone
                # cannot recover max_len for a windowed config (the row
                # count collapses to the window), so prefer the
                # recorded geometry and fall back to the region shape
                # for externally assembled pairs that left it unset.
                if program.paged is not None:
                    # Paged plans: pools are slot-agnostic, so geometry
                    # lives in the page table (slots rows) and the
                    # plan's virtual extent (pages_per_slot * page_size
                    # == max_len).
                    pt = next(s for s in program.decode.plan
                              .persistent_regions()
                              if s.name == "page_table")
                    checks = [(pt.shape,
                               (slots, program.paged.pages_per_slot)),
                              ((program.paged.cache_len,), (max_len,))]
                else:
                    # Generic named state: re-mint the engine config's
                    # own state specs through the family hook and
                    # demand the pair's persistent regions match name
                    # for name, shape for shape.  This catches what the
                    # recorded geometry alone cannot — a pair compiled
                    # from a *different* config (e.g. a windowed pair
                    # handed to a dense engine) whose slots/max_len
                    # happen to agree but whose region rows do not.
                    from ..core import regions as _regions
                    specs, _ = _regions.state_specs(cfg, slots, max_len)
                    want_specs = {s.name: s.shape for s in specs}
                    got_specs = {s.name: s.shape
                                 for s in (program.decode.plan
                                           .persistent_regions())}
                    checks = [(got_specs, want_specs)]
                if program.max_len is not None:
                    checks.append(((program.slots, program.max_len),
                                   (slots, max_len)))
                for got, want in checks:
                    if got != want:
                        raise ValueError(
                            f"ProgramPair compiled for slots/max_len "
                            f"{got}, engine configured for {want}")
            pair = program
            if pair is None:
                try:
                    pair = compile_program_pair(cfg, slots=slots,
                                                max_len=max_len,
                                                paged=paged,
                                                page_size=page_size,
                                                page_pool=page_pool,
                                                kv_quant=kv_quant)
                except NotImplementedError as e:
                    # Once per engine construction, never per tick.
                    # The lowering gate names the *specific* blocker
                    # (MoE dispatch, cross-attention, ...) — windowed
                    # attention is no longer one; it serves on the
                    # program path with window-sized KV regions.
                    self.fallback_reason = str(e)
                    # Structured twin of the warning below: a
                    # ``fallback`` flight event + a labeled gauge, so
                    # an exit-code-2 ``--program`` run is diagnosable
                    # from the metrics/flight artifacts alone.
                    self.obs.flight.event("fallback", reason=str(e))
                    self.obs.registry.gauge(
                        "serving_fallback",
                        help="1 when the engine fell back to the "
                             "legacy decode loop, labeled by blocker",
                        fallback_reason=str(e)).set(1)
                    warnings.warn(
                        f"no decode-Program lowering for {cfg.name} — "
                        f"{e}; serving through the legacy decode loop",
                        RuntimeWarning, stacklevel=2)
            if pair is not None:
                self.api = None
                self.cache = None
                self.program = pair
                self.state = executor.init_program_state(pair)
                # Families with admission-written persistent memory
                # (audio: read-only encoder cross K/V) expose
                # ``encode_memory`` on their ModelApi; admission runs
                # it once per request and scatters the returned rows
                # at the admitted slot *before* the prefill Program's
                # cross ops read them.
                fam_api = get_model(cfg)
                self._memory_writer = fam_api.encode_memory
                self._memory_input = fam_api.extra_input
                self._prefill = executor.jitted_prefill_runner(
                    pair.prefill, impl=impl)
                self._decode = executor.jitted_decode_runner(
                    pair.decode, impl=impl)
                if pair.paged is not None:
                    # Host-side page allocator: admission, on-demand
                    # decode pages, and COW forks are decided here
                    # between jitted calls; the device sees only the
                    # synced table and whole-page copies.
                    self._pool = executor.PagePool(pair.paged, slots)
                if chunk_size is not None:
                    if chunk_size < 1:
                        raise ValueError(
                            f"chunk_size must be >= 1, got {chunk_size}")
                    blocker = pair.chunk_blocker
                    if blocker is not None:
                        raise ValueError(
                            f"pair is not chunkable: {blocker}")
                self._chunk = (executor.jitted_chunk_runner(
                                   pair.prefill, impl=impl)
                               if (chunk_size is not None or spec_k)
                               else None)
                self._init_spec(pair, draft_cfg, draft_params)
                self._lm_program = True
                return
        if chunk_size is not None or spec_k:
            raise ValueError(
                "chunked prefill / speculative decode need the stateful "
                "LM Program path (use_program=True on a lowerable dense "
                f"config); blocked by: {self.fallback_reason or cfg.name}")
        if (program is not None and not lm) or isinstance(cfg, CNNConfig):
            # Program fast path (CNN workloads): one compiled Program
            # per batch size, executed whole per tick — no token cache.
            from ..models.cnn import compile_program
            from ..runtime.executor import jitted_runner
            self.api = None
            self.cache = None
            self.program = (program if program is not None
                            else compile_program(cfg, batch=slots))
            self._infer = jitted_runner(self.program, impl=impl)
            return
        self.program = None
        self.api = get_model(cfg)
        self.cache = self.api.init_cache(cfg, slots, max_len)
        self._decode = jax.jit(
            lambda p, c, t: self.api.decode_step(p, c, t, cfg, impl=impl))

    def _init_metrics(self) -> None:
        """Register the engine's metric families on the bundle's
        registry.  The legacy ``n_*`` attributes below are read-through
        properties over these counters — same numbers, one source of
        truth, and the whole plane serializes via
        ``obs.registry.snapshot()`` / ``prometheus_text()``."""
        m = self.obs.registry
        c, g, h = m.counter, m.gauge, m.histogram
        # Stateful-program counters: the program path prefills each
        # request exactly once at admission, so prefill_recomputes
        # stays 0 by construction (CI-asserted from the snapshot).
        self._c_prefills = c("serving_prefills_total")
        self._c_prefill_recomputes = c("serving_prefill_recomputes_total")
        self._c_decode_ticks = c("serving_decode_ticks_total")
        # Chunked-prefill / tick-liveness: a live slot the tick failed
        # to advance shows up in starved_ticks (stays 0 — chunking
        # exists precisely so admission can never stall decode).
        self._c_prefill_chunks = c("serving_prefill_chunks_total")
        self._c_starved = c("serving_starved_ticks_total")
        # Speculative decode: draft tokens proposed / accepted by
        # target verification, and ticks whose acceptance stopped
        # short of k (rollback).
        self._c_spec_proposed = c("serving_spec_proposed_total")
        self._c_spec_accepted = c("serving_spec_accepted_total")
        self._c_spec_rollbacks = c("serving_spec_rollbacks_total")
        # Paged KV: donor pages mapped at admission (prompt rows *not*
        # prefilled thanks to prefix sharing) and copy-on-write forks.
        self._c_shared_pages = c("serving_shared_pages_total")
        self._c_cow_forks = c("serving_cow_forks_total")
        self._c_requests = c("serving_requests_total",
                             help="requests submitted")
        self._c_finished = c("serving_requests_finished_total")
        self._c_tokens = c("serving_tokens_total",
                           help="generated tokens emitted")
        self._g_live = g("serving_live_slots")
        self._g_queue = g("serving_queue_depth")
        self._g_free_pages = g("serving_free_pages")
        self._h_tick = h("tick_ms", help="engine tick wallclock")
        self._h_ttft = h("ttft_ms", help="enqueue to first token")
        self._h_itl = h("itl_ms", help="inter-token latency")

    # Read-through compatibility properties: the counters moved onto
    # the metrics registry; every existing consumer (benchmarks, CI
    # greps, tests) still reads the same integers here.
    @property
    def n_prefills(self) -> int:
        return int(self._c_prefills.value)

    @property
    def n_prefill_recomputes(self) -> int:
        return int(self._c_prefill_recomputes.value)

    @property
    def n_decode_ticks(self) -> int:
        return int(self._c_decode_ticks.value)

    @property
    def n_prefill_chunks(self) -> int:
        return int(self._c_prefill_chunks.value)

    @property
    def n_starved_ticks(self) -> int:
        return int(self._c_starved.value)

    @property
    def n_spec_proposed(self) -> int:
        return int(self._c_spec_proposed.value)

    @property
    def n_spec_accepted(self) -> int:
        return int(self._c_spec_accepted.value)

    @property
    def n_spec_rollbacks(self) -> int:
        return int(self._c_spec_rollbacks.value)

    @property
    def n_shared_pages(self) -> int:
        return int(self._c_shared_pages.value)

    @property
    def n_cow_forks(self) -> int:
        return int(self._c_cow_forks.value)

    @property
    def on_program_path(self) -> bool:
        """True when LM tokens are served through the compiled
        (prefill, decode) Program pair — the public signal for callers
        that *require* the program path (launch/serve.py --program);
        False means the legacy decode loop, with ``fallback_reason``
        naming why."""
        return self._lm_program

    def _init_spec(self, pair, draft_cfg, draft_params) -> None:
        """Wire the speculative-decode draft pair: a second (prefill,
        decode) Program pair — ``compile_program_pair`` verbatim, same
        geometry — whose decode proposes ``spec_k`` tokens per tick for
        the target's batched verification."""
        if not self.spec_k:
            return
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if not self.greedy:
            raise ValueError(
                "speculative decode verifies greedy argmax proposals; "
                "sampling acceptance is out of scope (greedy=True)")
        if pair.paged is not None:
            raise NotImplementedError(
                "speculative decode over paged KV: the verify burst "
                "would need per-row page preparation (COW forks) "
                "inside the tick; serve paged configs without spec_k")
        if pair.caps is not None and not pair.caps.speculatable:
            raise NotImplementedError(
                f"speculative decode needs speculatable family state "
                f"({self.cfg.name} is family={self.cfg.family}): "
                f"rollback truncates lengths, which cannot rewind "
                f"recurrent or capacity-routed state")
        from ..models.transformer import compile_draft_pair
        from ..runtime import executor
        if draft_cfg is None:
            draft_cfg = self.cfg
            if draft_params is None:
                # Self-draft: the degenerate (but valid) configuration
                # where the draft is the target itself — every
                # proposal verifies, which is what the CI smoke pins.
                draft_params = self.params
        if draft_params is None:
            raise ValueError(
                f"draft_cfg {draft_cfg.name} needs draft_params "
                f"(the draft is a separate model)")
        dpair = compile_draft_pair(self.cfg, draft_cfg, slots=self.slots,
                                   max_len=self.max_len)
        self._draft_params = draft_params
        self._draft_pair = dpair
        self._draft_state = executor.init_program_state(dpair)
        self._draft_prefill = executor.jitted_prefill_runner(
            dpair.prefill, impl=self.impl)
        self._draft_decode = executor.jitted_decode_runner(
            dpair.decode, impl=self.impl)
        self._spec = True

    # -- admission -------------------------------------------------------------
    def submit(self, req: Request) -> AdmissionTicket:
        """Enqueue a request; returns the admission ticket (rejected
        with reason ``queue_full`` when the bounded queue is at
        capacity — the request is *not* held).  Stamps the enqueue
        time (TTFT starts here) and records the lifecycle events."""
        with self.obs.span("serving.submit"):
            return self._submit(req)

    def _submit(self, req: Request) -> AdmissionTicket:
        req._enqueue_t = self.obs.clock()
        self._c_requests.inc()
        prompt_len = (len(req.prompt)
                      if getattr(req.prompt, "ndim", 1) == 1 else 0)
        self.obs.flight.event("enqueue", uid=req.uid,
                              prompt_len=prompt_len)
        if self._lm_program:
            ticket = self.admission.submit(req)
        else:
            self.queue.append(req)
            ticket = AdmissionTicket(True, "queued", len(self.queue) - 1)
        self.obs.flight.event("admission", uid=req.uid,
                              accepted=ticket.accepted,
                              reason=ticket.reason,
                              position=ticket.position)
        return ticket

    def _free_slots(self):
        return [s for s in range(self.slots)
                if s not in self.live and s not in self._prefilling]

    def _admit(self):
        """Prefill queued requests into free slots through the decode
        path (slot-local prefill keeps the batch cache layout intact;
        batched prefill is the launch/steps.py path used for the large
        cells).

        All admissions in a tick are batched: one merge resets every
        admitted slot's cache, then the prompts are teacher-forced
        *together* — at prefill step t every admitted slot still inside
        its prompt advances at once, and a single masked merge per step
        folds exactly those slots' cache updates back (live slots'
        caches stay frozen).  Previously each admitted request ran a
        full ``jax.tree.map`` copy over all slots per prompt token."""
        admitted: list[tuple[int, Request]] = []
        for slot in self._free_slots():
            if not self.queue:
                break
            admitted.append((slot, self.queue.pop(0)))
        if not admitted:
            return
        self._reset_slots([slot for slot, _ in admitted])
        steps = max(len(req.prompt) - 1 for _, req in admitted)
        for t in range(steps):
            toks = np.zeros((self.slots,), np.int32)
            mask = np.zeros((self.slots,), bool)
            for slot, req in admitted:
                if t < len(req.prompt) - 1:
                    toks[slot] = int(req.prompt[t])
                    mask[slot] = True
            self._step_masked(jnp.asarray(toks), mask)
        for slot, req in admitted:
            req._last_token = int(req.prompt[-1])
            self.live[slot] = req

    @staticmethod
    def _batch_axis(leaf) -> int:
        """Model caches carry batch at axis 1 ((L, B, ...)); the shared
        ``pos`` vector is (B,)."""
        return 0 if leaf.ndim == 1 else 1

    def _reset_slots(self, slots: list[int]):
        """Zero the admitted slots' cache — one merge for all of them."""
        fresh = self.api.init_cache(self.cfg, 1, self.max_len)
        idx = np.asarray(slots)
        def put(c, f):
            axis = self._batch_axis(c)
            shape = list(c.shape)
            shape[axis] = len(idx)
            sel = [slice(None)] * c.ndim
            sel[axis] = idx
            return c.at[tuple(sel)].set(
                jnp.broadcast_to(f.astype(c.dtype), shape))
        self.cache = jax.tree.map(put, self.cache, fresh)

    def _step_masked(self, toks, mask: np.ndarray):
        """One batched decode step keeping only the masked slots' cache
        updates (the other slots' caches stay frozen)."""
        old_cache = self.cache
        logits, new_cache = self._decode(self.params, self.cache, toks)
        def merge(old, new):
            axis = self._batch_axis(old)
            shape = [1] * old.ndim
            shape[axis] = self.slots
            m = jnp.asarray(mask).reshape(shape)
            return jnp.where(m, new, old)
        self.cache = jax.tree.map(merge, old_cache, new_cache)
        return logits

    # -- program fast path (CNN) -------------------------------------------------
    def _program_step(self) -> list[Request]:
        """One tick on the program path: batch up to ``slots`` queued
        images, execute the compiled Program once, retire them all.
        ``out_tokens`` carries the argmax class id."""
        if not self.queue:
            return []
        span = self.obs.span
        with span("serving.stage"):
            batch = self.queue[:self.slots]
            self.queue = self.queue[self.slots:]
            images = np.stack([np.asarray(r.prompt) for r in batch])
            if len(batch) < self.slots:        # pad to the compiled batch
                pad = np.zeros((self.slots - len(batch),) + images.shape[1:],
                               images.dtype)
                images = np.concatenate([images, pad])
            images = jnp.asarray(images, self.cfg.jdtype)
        with span("serving.dispatch"):
            logits = self._infer(self.params, images)
        with span("serving.wait"):
            jax.block_until_ready(logits)
        with span("serving.fetch"):
            logits = np.asarray(logits)
        with span("serving.sample"):
            for r, lg in zip(batch, logits):
                r.out_tokens.append(int(np.argmax(lg)))
                r.done = True
        return batch

    # -- LM program fast path ----------------------------------------------------
    def _next_token(self, req: Request, logits_row: np.ndarray) -> int:
        if self.greedy:
            return int(np.argmax(logits_row))
        return int(np.random.default_rng(req.uid + len(req.out_tokens))
                   .choice(self.cfg.vocab, p=_softmax(logits_row)))

    def _emit_tokens(self, slot: int, req: Request, toks, finished: list,
                     ) -> int:
        """Append generated tokens in order until EOS or the request's
        budget retires it; returns how many were kept.  A speculative
        tick hands several accepted tokens at once — the truncation
        here is what keeps its output stream identical to the
        one-token-per-tick path."""
        kept = 0
        flight = self.obs.flight
        for nxt in toks:
            now = self.obs.clock()
            first = not req.out_tokens
            req.out_tokens.append(nxt)
            req._last_token = nxt
            kept += 1
            self._c_tokens.inc()
            if first:
                ttft_ms = (now - req._enqueue_t) * 1e3 \
                    if hasattr(req, "_enqueue_t") else 0.0
                self._h_ttft.observe(ttft_ms)
                flight.event("first_token", uid=req.uid, slot=slot,
                             token=nxt, ttft_ms=ttft_ms)
            else:
                itl_ms = (now - req._last_emit_t) * 1e3
                self._h_itl.observe(itl_ms)
                flight.event("token", uid=req.uid, slot=slot,
                             token=nxt, itl_ms=itl_ms)
            req._last_emit_t = now
            if ((self.eos is not None and nxt == self.eos)
                    or len(req.out_tokens) >= req.max_new_tokens):
                req.done = True
                finished.append(req)
                self.live.pop(slot, None)
                self._c_finished.inc()
                flight.event(
                    "release", uid=req.uid, slot=slot,
                    n_tokens=len(req.out_tokens),
                    reason=("eos" if (self.eos is not None
                                      and nxt == self.eos)
                            else "max_new_tokens"))
                if self._pool is not None:
                    # Retire the slot's pages: unref (a donor's shared
                    # prefix stays resident while any sharer holds a
                    # refcount) and drop it from the donor registry.
                    self._pool.release(slot)
                    self._slot_prompts.pop(slot, None)
                    self._slot_len.pop(slot, None)
                break
        return kept

    def _retire_if_done(self, slot: int, req: Request, nxt: int,
                        finished: list) -> None:
        self._emit_tokens(slot, req, [nxt], finished)

    def _lm_admit(self, finished: list) -> None:
        """Prefill queued prompts into free slots — once per request,
        ever.  Each admission runs the prefill Program: the full causal
        forward over the right-padded prompt, the block K/V written
        into the persistent cache regions at the slot, and the first
        generated token read off the prompt's last position.  Prompts
        longer than ``max_len`` condition on their most recent
        ``max_len`` tokens (the cache holds at most that much
        history).

        Free slots are recomputed per admission: a slot freed *during*
        this loop (EOS or ``max_new_tokens == 1`` on the prefill token
        retires the request inside ``_retire_if_done``) is immediately
        reusable for the next queued request instead of idling a
        tick.

        With ``chunk_size`` set, admission only *assigns* the slot and
        registers an ``_InFlightPrefill``; the prompt is prefilled one
        chunk per tick by ``_advance_prefills`` so decode ticks for the
        other slots interleave between chunks.  Admission stalls record
        their typed backpressure reason and — for pool exhaustion,
        where the request was already dequeued — requeue at the *head*
        so no later arrival can overtake a starved request."""
        flight = self.obs.flight
        while self.admission:
            free = self._free_slots()
            if not free:
                self.admission.note_blocked(adm.NO_FREE_SLOT)
                flight.event("admission", accepted=False,
                             reason=adm.NO_FREE_SLOT)
                break
            req = self.admission.pop()
            if req is None:
                break
            slot = free[0]
            if len(req.prompt) == 0:
                raise ValueError(f"request {req.uid}: empty prompt")
            win = np.asarray(req.prompt, np.int32)[-self.max_len:]
            write_from = 0
            if self._pool is not None:
                write_from = self._paged_admit(slot, win)
                if write_from is None:
                    # Pool exhausted: the request waits at the head of
                    # the queue until a retirement frees pages.
                    self.admission.requeue_front(req, adm.PAGES_EXHAUSTED)
                    flight.event("admission", accepted=False,
                                 reason=adm.PAGES_EXHAUSTED, uid=req.uid)
                    break
            flight.event("prefill_start", uid=req.uid, slot=slot,
                         length=len(win), write_from=write_from)
            if self._memory_writer is not None:
                self._write_encoder_memory(slot, req)
            if self.chunk_size is not None:
                padded = np.zeros((self.max_len,), np.int32)
                padded[:len(win)] = win
                # A fully page-shared prompt still owes the chunk that
                # computes the last row's logits (the write is
                # redirected, the first token is not).
                self._prefilling[slot] = _InFlightPrefill(
                    req=req, tokens=padded, length=len(win),
                    done=min(write_from, len(win) - 1),
                    write_from=write_from,
                    admitted_tick=self.n_decode_ticks)
                continue
            with self.obs.span("serving.prefill", uid=req.uid):
                padded = np.zeros((1, self.max_len), np.int32)
                padded[0, :len(win)] = win
                logits, self.state = self._prefill(
                    self.params, jnp.asarray(padded), self.state, slot,
                    len(win), write_from)
                self._finish_prefill(slot, req, padded,
                                     np.asarray(logits[0, len(win) - 1]),
                                     len(win), finished)

    def _write_encoder_memory(self, slot: int, req: Request) -> None:
        """Run the family's admission-time memory writer (the whisper
        encoder + cross K/V projection) over the request's ``extra``
        input and scatter the returned rows into the pair's read-only
        persistent regions at the admitted slot.  Happens before the
        prefill Program runs — its cross-attention ops read these
        regions — and exactly once per admission: the regions are
        ``read_only`` in the §5.1 plan, so no decode tick touches
        them until the slot is re-admitted."""
        if req.extra is None:
            raise ValueError(
                f"request {req.uid}: {self.cfg.family} serving needs "
                f"Request.extra ({self._memory_input}) to fill the "
                f"persistent encoder memory at admission")
        rows = self._memory_writer(
            self.params, jnp.asarray(req.extra, self.cfg.jdtype),
            self.cfg, impl=self.impl)
        persistent = self.program.persistent
        for name, row in rows.items():
            rid = persistent[name]
            buf = self.state.caches[rid]
            self.state.caches[rid] = buf.at[slot].set(row.astype(buf.dtype))

    def _finish_prefill(self, slot: int, req: Request, padded,
                        last_logits, length: int, finished: list) -> None:
        """Shared tail of both prefill flavors: accounting, the first
        generated token, liveness — and the draft prefill when
        speculative decode is on (the draft cache must hold the same
        history before it can propose)."""
        # Real accounting, not a constant: a second prefill of the
        # same request (any future re-admission/recompute path)
        # shows up here — CI asserts the count stays at zero.
        if getattr(req, "_prefilled", False):
            self._c_prefill_recomputes.inc()
        req._prefilled = True
        self._c_prefills.inc()
        self.live[slot] = req
        if self._spec:
            _, self._draft_state = self._draft_prefill(
                self._draft_params,
                jnp.asarray(padded.reshape(1, self.max_len)),
                self._draft_state, slot, length, 0)
        nxt = self._next_token(req, last_logits)
        self._retire_if_done(slot, req, nxt, finished)

    def _advance_prefills(self, finished: list) -> None:
        """Advance every in-flight chunked prefill by one chunk — a
        single batched chunk-Program call for all of them (they share
        the prefill Program, so the geometry always allows it).  An
        admission that reaches its prompt length emits its first token
        and goes live; by construction that happens within
        ``ceil(length / chunk_size)`` ticks of slot assignment."""
        if not self._prefilling:
            return
        items = sorted(self._prefilling.items())
        lengths = np.array([p.length for _, p in items], np.int32)
        starts = np.array([p.done for _, p in items], np.int32)
        stops = np.minimum(starts + self.chunk_size, lengths)
        logits, self.state = self._chunk(
            self.params,
            jnp.asarray(np.stack([p.tokens for _, p in items])),
            self.state,
            jnp.asarray(np.array([s for s, _ in items], np.int32)),
            jnp.asarray(starts), jnp.asarray(stops), jnp.asarray(lengths),
            jnp.asarray(np.array([p.write_from for _, p in items],
                                 np.int32)))
        self._c_prefill_chunks.inc(len(items))
        done_rows = None
        for i, (slot, p) in enumerate(items):
            self.obs.flight.event("prefill_chunk", uid=p.req.uid,
                                  slot=slot, start=int(starts[i]),
                                  stop=int(stops[i]))
            p.done = int(stops[i])
            if p.done < p.length:
                continue
            if done_rows is None:
                done_rows = np.asarray(logits)
            del self._prefilling[slot]
            self._finish_prefill(slot, p.req, p.tokens,
                                 done_rows[i, p.length - 1], p.length,
                                 finished)

    def _paged_admit(self, slot: int, win: np.ndarray) -> int | None:
        """Map an admitted prompt onto pool pages.  Finds the live
        donor with the longest *full-page* common prompt prefix,
        refcount-shares those donor pages into the slot's table row,
        and allocates fresh pages for the private remainder.  Returns
        ``write_from`` — the first prompt row the prefill Program
        actually writes (shared rows are scatter-redirected to the null
        page) — or None when the pool cannot hold the private pages.

        Donors whose ring write wrapped past ``max_len`` are skipped:
        the rolling overwrite has recycled their early pages, so the
        prompt is no longer resident there (sharers that mapped those
        pages *before* the wrap stay safe — the wrap write saw
        refcount > 1 and forked).  Donors still mid-chunked-prefill are
        skipped too: their prefix pages are mapped but not yet
        *written*, and a sharer's chunk would read rows the donor's
        later chunks still owe."""
        from ..runtime import executor
        pool = self._pool
        prompt = tuple(int(t) for t in win)
        shared: tuple[int, ...] = ()
        for s, donor in self._slot_prompts.items():
            if s in self._prefilling:
                continue
            if self._slot_len.get(s, 0) > pool.plan.cache_len:
                continue
            cand = pool.shared_prefix_pages(s, donor, prompt)
            if len(cand) > len(shared):
                shared = cand
        if not pool.can_admit(len(prompt), len(shared)):
            return None
        write_from = pool.admit(slot, len(prompt), shared)
        self._c_shared_pages.inc(len(shared))
        self._slot_prompts[slot] = prompt
        self._slot_len[slot] = len(prompt)
        executor.sync_page_table(self.state, self.program, pool)
        return write_from

    def _lm_program_step(self) -> list[Request]:
        """One tick on the stateful LM program path: prefill-admit
        queued requests (whole, or one chunk per tick when
        ``chunk_size`` is set), then advance every live slot through
        the decode Program — O(1) in prompt length, no recompute ever.
        The ProgramState (persistent cache buffers + per-slot lengths)
        is donated through the jitted runners, so the cache updates in
        place across ticks.

        The scheduling rule is decode-first fairness: slots live at
        tick start *always* get their decode advance this tick —
        admission only assigns slots and chunk work is bounded at
        ``chunk_size`` rows per in-flight prefill — so a long prompt
        can never stall the in-flight streams (``n_starved_ticks``
        counts violations; it stays 0 by construction)."""
        finished: list[Request] = []
        span = self.obs.span
        with span("serving.admit"):
            self._lm_admit(finished)
        if self._prefilling:
            with span("serving.chunk"):
                self._advance_prefills(finished)
        if not self.live:
            return finished
        starved = set(self.live)
        if self._pool is not None:
            with span("serving.pages"):
                self._prepare_pages()
        if self._spec:
            with span("serving.spec"):
                advanced = self._spec_tick(*self._decode_inputs(),
                                           finished)
        else:
            with span("serving.dispatch"):
                toks, occupied = self._decode_inputs()
                logits, self.state = self._decode(self.params,
                                                  jnp.asarray(toks),
                                                  self.state,
                                                  jnp.asarray(occupied))
                if self._pool is not None:
                    for slot in self.live:
                        self._slot_len[slot] += 1
            with span("serving.wait"):
                jax.block_until_ready(logits)
            with span("serving.fetch"):
                logits = np.asarray(logits)
            with span("serving.sample"):
                advanced = set()
                for slot, req in list(self.live.items()):
                    nxt = self._next_token(req, logits[slot])
                    self._retire_if_done(slot, req, nxt, finished)
                    advanced.add(slot)
        self._c_decode_ticks.inc()
        if starved - advanced:
            self._c_starved.inc(len(starved - advanced))
        return finished

    def _decode_inputs(self) -> tuple[np.ndarray, np.ndarray]:
        """Each slot's last token and the occupancy mask.  The mask
        keeps dead slots inert inside run_decode: no length advance, no
        cache-row write (slot-cache hygiene for the rolling-window
        plans, whose prefill does not rewrite the whole row region on
        re-admission)."""
        toks = np.zeros((self.slots,), np.int32)
        occupied = np.zeros((self.slots,), bool)
        for slot, req in self.live.items():
            toks[slot] = req._last_token
            occupied[slot] = True
        return toks, occupied

    def _prepare_pages(self) -> None:
        """Make each live slot's write page real and private before
        the jitted tick: allocate on-demand past the prompt, COW-fork
        shared pages (device page copy), then push the decided table."""
        from ..runtime import executor
        copies = []
        for slot in self.live:
            c = self._pool.prepare_decode(slot, self._slot_len[slot])
            if c is not None:
                copies.append(c)
                self.obs.flight.event("cow_fork", slot=slot,
                                      src_page=int(c[0]),
                                      dst_page=int(c[1]))
        executor.sync_page_table(self.state, self.program, self._pool)
        if copies:
            executor.apply_page_copies(self.state, self.program, copies)
            self._c_cow_forks.inc(len(copies))

    def _spec_tick(self, toks: np.ndarray, occupied: np.ndarray,
                   finished: list) -> set:
        """One speculative tick: the draft decode proposes up to
        ``spec_k`` tokens per live slot (k batched draft steps), the
        target verifies the whole burst in a single chunk-Program call
        per tick — rows ``[n, n + k_s]`` of each slot, standard greedy
        accept/rollback:

        * slot feeds ``[x0, d_1..d_k]``; target row ``n+j`` yields
          ``y_{j+1} = argmax`` — exactly what sequential decode would
          have produced given the prefix, because the verified rows'
          K/V are written by the same pass;
        * accept the longest prefix with ``d_j == y_j`` (``a`` tokens),
          emit ``y_1..y_{a+1}`` (the first mismatch is *corrected*, not
          discarded — a >= 0 tokens always advance);
        * rollback = truncate both pairs' lengths to ``n + a + 1``;
          rows past the truncation are unattendable (``ring_kv_len``)
          and the next tick's write overwrites the first stale row.

        Returns the set of slots that advanced (all live ones)."""
        from ..runtime import executor
        lens = np.asarray(self.state.lengths)
        all_live = sorted(self.live)
        # Slots whose absolute position reached max_len decode through
        # the ring (rolling overwrite) — the verify chunk is row-
        # addressed, so they take a plain decode step this tick.
        live_slots = [s for s in all_live if int(lens[s]) < self.max_len]
        wrapped = [s for s in all_live if int(lens[s]) >= self.max_len]
        advanced = set()
        if wrapped:
            wmask = np.zeros((self.slots,), bool)
            wmask[wrapped] = True
            wlogits, self.state = self._decode(self.params,
                                               jnp.asarray(toks),
                                               self.state,
                                               jnp.asarray(wmask))
            wlogits = np.asarray(wlogits)
            for s in wrapped:
                req = self.live[s]
                self._retire_if_done(s, req,
                                     self._next_token(req, wlogits[s]),
                                     finished)
                advanced.add(s)
        if not live_slots:
            return advanced
        # Per-slot burst: the verify writes rows [n, n+k_s], so cap at
        # the compiled max_len; a slot at the boundary degenerates to
        # k_s = 0 — a plain (verified) single-token step.
        k_s = {s: max(0, min(self.spec_k, self.max_len - 1 - int(lens[s])))
               for s in live_slots}
        max_k = max(k_s.values())
        # Draft proposal rounds: round i feeds the previous proposal
        # and advances only the slots still inside their burst.
        proposals = {s: [] for s in live_slots}
        cur = toks.copy()
        for i in range(max_k):
            dmask = np.zeros((self.slots,), bool)
            for s in live_slots:
                dmask[s] = i < k_s[s]
            dlogits, self._draft_state = self._draft_decode(
                self._draft_params, jnp.asarray(cur), self._draft_state,
                jnp.asarray(dmask))
            dlogits = np.asarray(dlogits)
            for s in live_slots:
                if i < k_s[s]:
                    d = int(np.argmax(dlogits[s]))
                    proposals[s].append(d)
                    cur[s] = d
        # Target verification: one batched chunk call over all live
        # slots — slot rows [n, n+k_s] carry [x0, d_1..d_k]; length is
        # pinned past stop so no final-chunk tail write triggers.
        B = len(live_slots)
        vtoks = np.zeros((B, self.max_len), np.int32)
        starts = np.zeros((B,), np.int32)
        stops = np.zeros((B,), np.int32)
        for i, s in enumerate(live_slots):
            n = int(lens[s])
            starts[i], stops[i] = n, n + k_s[s] + 1
            vtoks[i, n] = toks[s]
            for j, d in enumerate(proposals[s]):
                vtoks[i, n + 1 + j] = d
        vlogits, self.state = self._chunk(
            self.params, jnp.asarray(vtoks), self.state,
            jnp.asarray(np.array(live_slots, np.int32)),
            jnp.asarray(starts), jnp.asarray(stops),
            jnp.asarray(np.full((B,), self.max_len + 1, np.int32)),
            jnp.asarray(np.zeros((B,), np.int32)))
        vlogits = np.asarray(vlogits)
        # Accept / emit / rollback, then mirror the rolled-back lengths
        # into the draft state so the next burst proposes from the
        # accepted history.
        new_lens = np.asarray(self.state.lengths).copy()
        for i, s in enumerate(live_slots):
            req = self.live[s]
            n = int(lens[s])
            y = [int(np.argmax(vlogits[i, n + j]))
                 for j in range(k_s[s] + 1)]
            a = 0
            while a < k_s[s] and proposals[s][a] == y[a]:
                a += 1
            self._c_spec_proposed.inc(k_s[s])
            self._c_spec_accepted.inc(a)
            if a < k_s[s]:
                self._c_spec_rollbacks.inc()
            self.obs.flight.event("spec", slot=s, uid=req.uid,
                                  proposed=k_s[s], accepted=a,
                                  rollback=a < k_s[s])
            kept = self._emit_tokens(s, req, y[:a + 1], finished)
            new_lens[s] = n + kept
            advanced.add(s)
        # Two separate device arrays: the states are donated through
        # different runner calls, so they must never share a buffer.
        self.state = executor.ProgramState(self.state.caches,
                                           jnp.asarray(new_lens))
        self._draft_state = executor.ProgramState(
            self._draft_state.caches, jnp.asarray(new_lens))
        return advanced

    # -- decode ------------------------------------------------------------------
    def step(self) -> list[Request]:
        """One engine tick: admit, decode one token for all live slots,
        retire finished requests.  Returns requests finished this tick.

        Every tick is a ``serving.step`` span, whose duration feeds the
        ``tick_ms`` histogram, and — when a flight recorder is attached
        — lands one ``tick`` snapshot event (live slots, queue depth,
        free pages, cumulative starved ticks), the engine-level
        heartbeat the flight replay and the console dashboard read."""
        with self.obs.span("serving.step") as tick:
            finished = self._step_inner()
        dt_ms = tick.seconds * 1e3
        self._tick_no += 1
        self._h_tick.observe(dt_ms)
        qd = len(self.admission) if self._lm_program else len(self.queue)
        self._g_live.set(len(self.live))
        self._g_queue.set(qd)
        free_pages = self._pool.free_pages if self._pool is not None else -1
        self._g_free_pages.set(free_pages)
        self.obs.flight.event(
            "tick", tick=self._tick_no, dt_ms=dt_ms, live=len(self.live),
            queue_depth=qd, free_pages=free_pages,
            starved=int(self._c_starved.value))
        return finished

    def dashboard_line(self) -> str:
        """One-line console dashboard: the numbers an operator watches,
        read off the same registry the artifacts serialize."""
        snap_p = self._h_ttft.percentile
        itl_p = self._h_itl.percentile
        return (f"tick {self._tick_no:>6} | live {len(self.live):>3} "
                f"| queue {int(self._g_queue.value):>3} "
                f"| toks {int(self._c_tokens.value):>7} "
                f"| ttft_p50 {snap_p(50.0):8.1f}ms "
                f"| itl_p50 {itl_p(50.0):7.2f}ms "
                f"| starved {int(self._c_starved.value)}")

    def _step_inner(self) -> list[Request]:
        if self._lm_program:
            return self._lm_program_step()
        if self.program is not None:
            return self._program_step()
        self._admit()
        if not self.live:
            return []
        toks = np.zeros((self.slots,), np.int32)
        for slot, req in self.live.items():
            toks[slot] = req._last_token
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks))
        logits = np.asarray(logits)
        finished: list[Request] = []
        for slot, req in list(self.live.items()):
            nxt = self._next_token(req, logits[slot])
            self._retire_if_done(slot, req, nxt, finished)
        return finished

    def run_until_drained(self, max_ticks: int = 10_000) -> list[Request]:
        done = []
        for _ in range(max_ticks):
            done.extend(self.step())
            if (not self.live and not self.queue and not self.admission
                    and not self._prefilling):
                break
        return done


def _softmax(x):
    x = x - x.max()
    e = np.exp(x)
    return e / e.sum()
