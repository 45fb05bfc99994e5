"""Stage-8 observability suite: histogram percentiles vs a numpy
oracle, flight-recorder schema round-trip + replay, TTFT/ITL under a
fake clock, counters-match-legacy parity on a full engine run, and the
disabled-mode zero-overhead contract."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import init_params, transformer
from repro.obs import (EVENT_FIELDS, NULL, Counter, FlightRecorder,
                       Gauge, Histogram, MetricsRegistry, Observability,
                       exp_buckets, parse_events, read_events,
                       replay_summary)
from repro.serving import Request, ServingEngine

K0 = jax.random.PRNGKey(0)


def _cfg(name="smollm-360m", **over):
    cfg = REGISTRY[name].smoke()
    return dataclasses.replace(cfg, **over) if over else cfg


# --- metrics: primitives -----------------------------------------------------------

def test_counter_monotone():
    c = Counter()
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write_wins():
    g = Gauge()
    g.set(7)
    g.inc(2)
    g.dec()
    assert g.value == 8


def test_histogram_percentiles_vs_numpy_oracle():
    """Fine linear buckets => the interpolated percentile must land
    within one bucket width of np.percentile, across distributions."""
    rng = np.random.default_rng(0)
    edges = [float(x) for x in np.linspace(0.5, 500.0, 1000)]
    width = edges[1] - edges[0]
    for sample in (rng.uniform(1, 400, 5000),
                   rng.exponential(40, 5000) + 1,
                   rng.normal(200, 30, 5000).clip(1, 499)):
        h = Histogram(edges)
        for v in sample:
            h.observe(float(v))
        for q in (1, 10, 25, 50, 75, 90, 99, 99.9):
            # Bracket numpy's order-statistic interpolation: the
            # histogram knows values only to bucket resolution, and in
            # sparse tails adjacent order stats are further apart than
            # a bucket — so the bound is [lower, higher] +- one width.
            lo = float(np.percentile(sample, q, method="lower"))
            hi = float(np.percentile(sample, q, method="higher"))
            got = h.percentile(q)
            assert lo - width - 1e-9 <= got <= hi + width + 1e-9, \
                (q, got, lo, hi)
        assert h.count == len(sample)
        assert h.sum == pytest.approx(float(sample.sum()))
        assert h.mean == pytest.approx(float(sample.mean()))


def test_histogram_overflow_floors_at_last_edge():
    h = Histogram([1.0, 2.0, 4.0])
    for v in (10.0, 20.0, 30.0):
        h.observe(v)
    assert h.saturated == 3
    assert h.percentile(50) == 4.0            # floored, never invented
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        Histogram([2.0, 1.0])                 # must be ascending


def test_exp_buckets_geometric():
    b = exp_buckets(1.0, 16.0, factor=2.0)
    assert b == [1.0, 2.0, 4.0, 8.0, 16.0]


# --- metrics: registry -------------------------------------------------------------

def test_registry_register_or_fetch_and_labels():
    m = MetricsRegistry()
    c1 = m.counter("reqs_total", reason="a")
    c2 = m.counter("reqs_total", reason="a")
    c3 = m.counter("reqs_total", reason="b")
    assert c1 is c2 and c1 is not c3
    c1.inc(2)
    c3.inc()
    snap = m.snapshot()
    assert snap["counters"]['reqs_total{reason="a"}'] == 2
    assert snap["counters"]['reqs_total{reason="b"}'] == 1
    with pytest.raises(ValueError):
        m.gauge("reqs_total")                 # kind collision


def test_registry_snapshot_and_prometheus_text():
    m = MetricsRegistry()
    m.counter("c_total", help="a counter").inc(3)
    m.gauge("g").set(1.5)
    h = m.histogram("h_ms", buckets=[1.0, 10.0])
    h.observe(0.5)
    h.observe(5.0)
    h.observe(50.0)
    snap = m.snapshot()
    assert snap["histograms"]["h_ms"]["count"] == 3
    assert snap["histograms"]["h_ms"]["counts"] == [1, 1, 1]
    text = m.prometheus_text()
    assert "# TYPE c_total counter" in text
    assert "c_total 3" in text
    assert 'h_ms_bucket{le="+Inf"} 3' in text
    assert "h_ms_count 3" in text
    # round-trips as JSON with a meta header
    doc = json.loads(m.to_json(run="test"))
    assert doc["meta"]["run"] == "test"
    assert doc["counters"]["c_total"] == 3


# --- flight recorder ---------------------------------------------------------------

def test_flight_schema_enforced_at_emit():
    fr = FlightRecorder()
    with pytest.raises(ValueError, match="unknown flight event"):
        fr.event("warp_drive", engaged=True)
    with pytest.raises(ValueError, match="missing required"):
        fr.event("enqueue", uid=1)            # prompt_len missing
    fr.event("enqueue", uid=1, prompt_len=4)
    assert fr.events[0]["ev"] == "enqueue"
    assert "t" in fr.events[0]


def test_flight_roundtrip_write_parse_replay(tmp_path):
    """Write a synthetic lifecycle to disk, parse it back, and check
    the replay reconstructs the token stream and totals."""
    path = tmp_path / "flight.jsonl"
    t = iter(np.arange(0.0, 10.0, 0.25))
    fr = FlightRecorder(path, clock=lambda: float(next(t)))
    fr.event("enqueue", uid=7, prompt_len=3)
    fr.event("admission", uid=7, accepted=True, reason="queued")
    fr.event("prefill_start", uid=7, slot=0, length=3, write_from=0)
    fr.event("prefill_chunk", uid=7, slot=0, start=0, stop=3)
    fr.event("first_token", uid=7, slot=0, token=11, ttft_ms=750.0)
    fr.event("token", uid=7, slot=0, token=12, itl_ms=250.0)
    fr.event("release", uid=7, slot=0, n_tokens=2, reason="eos")
    fr.event("tick", tick=1, dt_ms=1.0, live=0, queue_depth=0,
             free_pages=-1, starved=0)
    fr.close()
    events = read_events(path)
    assert [e["ev"] for e in events] == [e["ev"] for e in fr.events]
    summ = replay_summary(events)
    req = summ["requests"][7]
    assert req["tokens"] == [11, 12]
    assert req["release_reason"] == "eos"
    assert req["chunks"] == 1
    assert summ["totals"]["n_released"] == 1
    assert summ["totals"]["n_tokens"] == 2


def test_flight_parse_rejects_malformed():
    with pytest.raises(ValueError, match="unknown event type"):
        parse_events('{"ev": "nope", "t": 0}')
    with pytest.raises(ValueError, match="missing"):
        parse_events('{"ev": "enqueue", "uid": 1}')


def test_replay_ttft_itl_from_fake_clock():
    """TTFT/ITL are *recomputed* from event timestamps — feed a fake
    clock and check the replay agrees with it, independent of the
    recorded ttft_ms/itl_ms fields (which we deliberately corrupt)."""
    times = iter([0.0, 1.0, 1.5, 1.75, 2.0])
    fr = FlightRecorder(clock=lambda: next(times))
    fr.event("enqueue", uid=1, prompt_len=2)                 # t=0.0
    fr.event("admission", uid=1, accepted=True, reason="queued")
    fr.event("first_token", uid=1, slot=0, token=5, ttft_ms=-1.0)
    fr.event("token", uid=1, slot=0, token=6, itl_ms=-1.0)   # t=1.75
    fr.event("token", uid=1, slot=0, token=7, itl_ms=-1.0)   # t=2.0
    summ = replay_summary(fr.events)
    req = summ["requests"][1]
    assert req["ttft_ms"] == pytest.approx(1500.0)           # 0.0→1.5
    assert req["itl_ms"] == pytest.approx([250.0, 250.0])


def test_replay_raises_on_token_count_mismatch():
    fr = FlightRecorder(clock=lambda: 0.0)
    fr.event("enqueue", uid=1, prompt_len=2)
    fr.event("first_token", uid=1, slot=0, token=5, ttft_ms=1.0)
    fr.event("release", uid=1, slot=0, n_tokens=3, reason="eos")
    with pytest.raises(ValueError, match="replayed"):
        replay_summary(fr.events)


def test_event_taxonomy_is_closed():
    """Every event type the engine emits is in the schema — adding an
    emit site without extending EVENT_FIELDS is a ValueError at emit
    time, so this pin is about deletions/renames."""
    assert set(EVENT_FIELDS) >= {
        "enqueue", "admission", "prefill_start", "prefill_chunk",
        "first_token", "token", "spec", "cow_fork", "release", "tick",
        "fallback"}


# --- engine integration ------------------------------------------------------------

_ENG_CFG = _cfg(n_layers=2)
_ENG_PARAMS = init_params(transformer.param_defs(_ENG_CFG), K0)


def _run_engine(obs=None, **eng_over):
    eng = ServingEngine(_ENG_CFG, _ENG_PARAMS, slots=2, max_len=32,
                        impl="reference", use_program=True,
                        chunk_size=8, obs=obs, **eng_over)
    rng = np.random.default_rng(3)
    reqs = [Request(uid=i,
                    prompt=rng.integers(1, _ENG_CFG.vocab,
                                        size=4 + i).astype(np.int32),
                    max_new_tokens=4)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run_until_drained()
    return eng, reqs, done


def test_engine_counters_match_legacy_properties():
    """Full engine run: the read-through n_* properties and the
    registry snapshot are the same numbers — one source of truth."""
    obs = Observability(flight_path=None)
    eng, reqs, done = _run_engine(obs=obs)
    snap = obs.registry.snapshot()
    c = snap["counters"]
    assert eng.n_prefills == c["serving_prefills_total"] == 3
    assert eng.n_prefill_recomputes == \
        c["serving_prefill_recomputes_total"] == 0
    assert eng.n_decode_ticks == c["serving_decode_ticks_total"] > 0
    assert eng.n_prefill_chunks == c["serving_prefill_chunks_total"] > 0
    assert eng.n_starved_ticks == c["serving_starved_ticks_total"] == 0
    assert c["serving_tokens_total"] == \
        sum(len(r.out_tokens) for r in done) == 12
    assert c["serving_requests_finished_total"] == 3
    # latency plane populated: one TTFT per request, ITL for the rest
    assert snap["histograms"]["ttft_ms"]["count"] == 3
    assert snap["histograms"]["itl_ms"]["count"] == 9
    assert snap["histograms"]["tick_ms"]["count"] == eng._tick_no
    assert eng.dashboard_line().startswith("tick")


def test_engine_flight_replay_matches_token_streams(tmp_path):
    """The flight record replays to *exactly* the engine's emitted
    token streams, and the JSONL file parses back to the same events."""
    path = tmp_path / "flight.jsonl"
    obs = Observability(flight_path=str(path))
    eng, reqs, done = _run_engine(obs=obs)
    obs.close()
    summ = replay_summary(obs.flight.events)
    assert set(summ["requests"]) == {r.uid for r in reqs}
    for r in reqs:
        assert summ["requests"][r.uid]["tokens"] == r.out_tokens
        assert summ["requests"][r.uid]["prompt_len"] == len(r.prompt)
        assert summ["requests"][r.uid]["release_reason"] is not None
    assert summ["totals"]["n_tokens"] == \
        sum(len(r.out_tokens) for r in done)
    disk = read_events(path)
    assert len(disk) == len(obs.flight.events)
    assert [e["ev"] for e in disk] == [e["ev"] for e in obs.flight.events]


def test_disabled_mode_zero_events_no_sampler():
    """Default Observability: NULL recorder accumulates nothing."""
    eng, reqs, done = _run_engine()            # default obs
    assert eng.obs.flight is NULL
    assert eng.obs.flight.events == ()
    assert not eng.obs.flight_enabled
    assert sum(len(r.out_tokens) for r in done) == 12


def test_admission_counters_on_registry():
    """AdmissionQueue accounting lives on the engine's registry; the
    legacy attributes read through."""
    obs = Observability()
    eng, reqs, done = _run_engine(obs=obs, queue_capacity=1)
    # capacity 1 with 3 submits => at least one queue_full bounce
    assert eng.admission.n_rejected >= 1
    snap = obs.registry.snapshot()
    assert snap["counters"]["admission_rejected_total"] == \
        eng.admission.n_rejected
    assert eng.admission.blocked["queue_full"] >= 1
