"""Replay harness — re-execute one traced ProgramOp, optionally with a
*candidate* schedule substituted.

This is the autotuner's measurement primitive (byteprofile-style: the
trace records what ran; replay re-runs it in isolation).  A trace
record (``runtime/executor.TraceRecord``) fully determines an op's
dispatch — kind, resolved schedule, operand shapes/dtypes — so a
single op can be rebuilt and timed without its Program, its params, or
its upstream activations: operands are synthesized at the recorded
shapes, regions are remapped to a private id space, and the param path
is rewritten to a flat ``"p"``/``"p_b"`` dict.  Execution goes through
the *same* ``_run_op`` / ``_run_decode_attention`` dispatch the
executor uses, so a replayed op cannot drift from what ``run`` would
do (replay-vs-executor parity is a tier-1 test).

``candidate`` substitutes schedule decisions before dispatch — conv
(out_rows, kernels_per_tile, strip_storage), matmul (dataflow, block),
attention (block_q, block_kv) — which is exactly how
``core/autotune.py`` measures a candidate it is considering: schedule
decisions change *where bytes move*, never the math, so the replayed
output must match the incumbent's bit-for-bit (reference impl) or to
kernel tolerance (pallas).

The module is also a CLI: ``python -m repro.runtime.replay TRACE.jsonl``
prints the measured-vs-predicted error table per kernel kind, before
and after calibration (``core/cost.fit_cost_model``).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from ..core.dataflow import Dataflow
from ..core.program import AttentionSpec, ProgramOp
from ..core.regions import kv_cache_rows
from ..core.tiling import ConvTiling
from .executor import (_FAMILY_KERNELS, TraceRecord,
                       _run_decode_attention, _run_op, _time_thunk)

__all__ = ["op_from_record", "synth_operands", "replay_record",
           "replay_outputs", "error_report"]

# Private region-id space for rebuilt ops (never collides with a real
# plan: replay builds its own regions dict).
_RID = {"in": 0, "k": 1, "v": 2, "in2": 3, "bypass": 4, "out": 9,
        "k_cache": 10, "v_cache": 11}


def op_from_record(record: TraceRecord | dict,
                   candidate: dict | None = None) -> ProgramOp:
    """Rebuild an executable ProgramOp from a trace record, with
    ``candidate`` schedule decisions substituted.

    Candidate keys (all optional): ``conv_tiling`` (ConvTiling or its
    asdict), ``strip_storage``, ``dataflow`` (Dataflow or its value),
    ``block`` ((bm, bk, bn)), ``block_q``, ``block_kv``.
    """
    r = record if isinstance(record, TraceRecord) else \
        TraceRecord.from_dict(record)
    s = dict(r.schedule)
    if candidate:
        s.update({k: v for k, v in candidate.items()
                  if k not in ("block_q", "block_kv")})
    ct = s.get("conv_tiling")
    if isinstance(ct, dict):
        ct = ConvTiling(**ct)
    df = s.get("dataflow")
    if isinstance(df, str):
        df = Dataflow(df)
    block = tuple(s["block"]) if s.get("block") else None
    attn = None
    if s.get("attn"):
        a = dict(s["attn"])
        if candidate:
            for k in ("block_q", "block_kv"):
                if k in candidate:
                    a[k] = candidate[k]
        attn = AttentionSpec(**a)
    # Keep the op's strip_storage consistent with a substituted tiling.
    strip = s.get("strip_storage")
    if ct is not None and candidate and "conv_tiling" in candidate:
        strip = ct.strip_storage
    has_bypass = s.get("fuse_bypass") and "bypass" in r.operands
    return ProgramOp(
        index=0, name=r.name, kernel=r.kind,
        in_region=_RID["in"], out_region=_RID["out"],
        param_key="p" if ("w" in r.operands or r.kind == "embed") else None,
        param_key_b="p_b" if "b" in r.operands and r.kind == "norm" else None,
        bypass_region=_RID["bypass"] if has_bypass else None,
        k_region=_RID["k"] if "k" in r.operands else None,
        v_region=_RID["v"] if "v" in r.operands else None,
        in2_region=_RID["in2"] if "in2" in r.operands else None,
        k_cache_region=_RID["k_cache"] if "k_cache" in r.operands else None,
        v_cache_region=_RID["v_cache"] if "v_cache" in r.operands else None,
        stride=s.get("stride", 1), pad=s.get("pad", 0),
        window=s.get("window", 0),
        fuse_bias=s.get("fuse_bias", False),
        fuse_activation=s.get("fuse_activation"),
        fuse_bypass=bool(has_bypass),
        bypass_first=s.get("bypass_first", True),
        fuse_pool=tuple(s["fuse_pool"]) if s.get("fuse_pool") else None,
        strip_storage=strip, dataflow=df, conv_tiling=ct, block=block,
        attn=attn, norm_kind=s.get("norm_kind"),
        flatten_input=s.get("flatten_input", False),
        transpose_w=s.get("transpose_w", False),
        flops=r.flops, traffic_bytes=r.traffic_bytes,
        exec_time_s=r.modeled_time_s)


def _synth(shape, dtype, key, *, vocab: int | None = None):
    shape = tuple(shape)
    jdt = jnp.dtype(dtype)
    if jdt.kind in "iu":
        return jax.random.randint(key, shape, 0, max(vocab or 2, 2),
                                  dtype=jdt)
    if jdt == jnp.bool_:
        return jnp.ones(shape, bool)
    return jax.random.normal(key, shape, jnp.float32).astype(jdt) * 0.1


def synth_operands(record: TraceRecord | dict, seed: int = 0
                   ) -> tuple[dict, dict]:
    """(regions, params) with random arrays at the recorded shapes,
    deterministic per seed.  Token inputs (int dtypes) draw from the
    recorded embed-table row count when present."""
    r = record if isinstance(record, TraceRecord) else \
        TraceRecord.from_dict(record)
    vocab = r.operands["w"][0][0] if r.kind == "embed" else None
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    regions: dict[int, jax.Array] = {}
    for role in ("in", "k", "v", "in2", "bypass", "k_cache", "v_cache"):
        if role in r.operands:
            shape, dt = r.operands[role]
            regions[_RID[role]] = _synth(shape, dt, next(keys), vocab=vocab)
    params: dict = {}
    if "w" in r.operands:
        flag = r.operands.get("param_dict")
        w = _synth(*r.operands["w"], next(keys))
        if flag and flag[1] == "dict":
            params["p"] = {"w": w}
            if "b" in r.operands:
                params["p"]["b"] = _synth(*r.operands["b"], next(keys))
        else:
            params["p"] = w
            if "b" in r.operands:          # norm bias rides separately
                params["p_b"] = _synth(*r.operands["b"], next(keys))
    return regions, params


def replay_outputs(record: TraceRecord | dict, *,
                   candidate: dict | None = None, impl: str = "auto",
                   interpret: bool | None = None, seed: int = 0):
    """Execute the rebuilt op once; returns its output array (decode
    ops: the attention output, cache updates discarded).  Same seed =>
    same synthetic operands, so two candidates' outputs are directly
    comparable."""
    out, _ = replay_record(record, candidate=candidate, impl=impl,
                           interpret=interpret, seed=seed, measure=False)
    return out


def replay_record(record: TraceRecord | dict, *,
                  candidate: dict | None = None, impl: str = "auto",
                  interpret: bool | None = None, repeats: int = 3,
                  measure: bool = True, seed: int = 0):
    """(output, measured_time_s | None) for one rebuilt op.

    The measurement is ``_time_thunk``'s min-of-repeats with
    block-until-ready, the same clock the trace recorder uses — so a
    replayed incumbent reproduces its traced wallclock up to noise, and
    candidates are ranked on an equal footing.
    """
    r = record if isinstance(record, TraceRecord) else \
        TraceRecord.from_dict(record)
    if r.kind in _FAMILY_KERNELS:
        # Family ops carry whole-block param subtrees and persistent
        # state rows the record does not serialize, so they cannot be
        # rebuilt in isolation.  The autotuner never proposes
        # candidates for them (autotune.TUNABLE), and the error-table
        # path (``error_report``) is record-dict based — calibration
        # still fits these kinds from their traced measurements.
        raise NotImplementedError(
            f"replay of family op kind {r.kind!r}: not rebuildable "
            f"from a trace record (block param subtree + persistent "
            f"state); these kinds are identity-only in the autotuner")
    op = op_from_record(r, candidate)
    regions, params = synth_operands(r, seed)
    if r.kind == "decode_attention":
        shape = r.operands["k_cache"][0]
        slots = shape[0]
        cache_len = kv_cache_rows(shape, op.attn.kv_fold)
        pos = jnp.asarray(r.extras.get("pos", [cache_len // 2] * slots),
                          jnp.int32)
        live = jnp.asarray(r.extras.get("live", [True] * slots), bool)

        def thunk():
            return _run_decode_attention(
                op, regions[op.in_region], regions[op.k_region],
                regions[op.v_region], regions[op.k_cache_region],
                regions[op.v_cache_region], pos, live, impl=impl,
                interpret=interpret)

        out = thunk()[0]
    else:
        def thunk():
            return _run_op(op, regions[op.in_region], regions, params,
                           impl=impl, interpret=interpret)

        out = thunk()
    t = _time_thunk(thunk, repeats) if measure else None
    return out, t


def error_report(trace, calibrate: bool = True) -> tuple[list[dict], str]:
    """(rows, rendered table) of measured-vs-predicted error per kernel
    kind for a trace — the harness's headline artifact.  With
    ``calibrate`` the table also shows the post-fit error of
    ``core/cost.fit_cost_model`` on the same records."""
    from ..core.cost import error_table, fit_cost_model, format_error_table
    recs = trace.record_dicts()
    model = fit_cost_model(recs) if calibrate else None
    rows = error_table(recs, model)
    return rows, format_error_table(rows)


def main(argv=None) -> int:
    from .executor import ExecutorTrace
    ap = argparse.ArgumentParser(
        description="measured-vs-predicted error table for a trace")
    ap.add_argument("trace", help="JSONL trace from trace_program(...).save")
    ap.add_argument("--no-calibrate", action="store_true",
                    help="skip the least-squares fit column")
    ap.add_argument("--json", metavar="PATH",
                    help="also write the table rows as JSON")
    args = ap.parse_args(argv)
    trace = ExecutorTrace.load(args.trace)
    rows, table = error_report(trace, calibrate=not args.no_calibrate)
    print(f"trace {args.trace}: program {trace.program} on {trace.hw} "
          f"(impl={trace.impl}, repeats={trace.repeats})")
    print(table)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
