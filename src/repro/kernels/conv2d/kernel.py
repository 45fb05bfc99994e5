"""Row-strip implicit-GEMM conv2d Pallas kernels — the paper's own
workload, scheduled the paper's way, with the overlap-storage decision
(duplicate vs re-fetch) lifted to a compiler choice.

Maps are tiled at *output-row-strip* granularity (T2).  Two kernels
realize the same schedule with different halo storage:

* ``conv2d_virtual_pallas`` — **zero-copy (default)**: the kernel
  receives the whole padded per-image maps as one VMEM-resident block
  (grid-blocked only on batch / output channels) and gathers each
  output-row strip *inside* the kernel body with a dynamic slice keyed
  off the strip program id.  Strip row offsets are affine
  (``s * out_rows``); when a caller needs non-affine offsets
  (ragged strip tables) it passes ``row_starts`` and the offsets are
  scalar-prefetched via ``PrefetchScalarGridSpec`` so the DMA address
  is known before the body runs.  No halo byte is ever duplicated in
  HBM.  An optional fused maxpool epilogue (``pool=(window, stride,
  pad)``) pools the conv output before writeback — the strip computes
  the few extra conv rows each overlapping pool window needs, trading
  a sliver of recompute for the pool layer's entire HBM round trip.

* ``conv2d_strips_pallas`` — the paper-faithful baseline: ops.py
  materializes halo-augmented input strips in HBM (Snowflake stores
  overlapped regions in DRAM because its DMA engine needs contiguous
  single-burst loads) and the kernel consumes one ``(in_rows, W, Cin)``
  strip per grid row.  Kept for the strip-storage benchmark and for
  hardware whose DMA truly requires contiguous strips.

Kernels (weights) are tiled at whole-kernel granularity, ``kpt`` output
channels per tile.  The Mloop/Kloop choice (T3) is the grid order:

* MAPS_RESIDENT  (Kloop): strip/batch block index ignores the kernel
  tile, so the maps block stays resident while kernel tiles stream.
* WEIGHTS_RESIDENT (Mloop): the weight tile stays resident while
  strips stream.

Every grid dimension writes a disjoint output block and carries no
cross-iteration state, so all dimensions are declared ``"parallel"``
in ``compiler_params`` — Mosaic is free to double-buffer and reorder.

The conv itself is implicit GEMM: for each (dy, dx) tap, a patch of
the strip is contracted with w[dy, dx] on the MXU and accumulated in
f32.  The kernels see unit-stride convs only: ops.py folds a stride s
into channels first (space-to-depth), which turns a k x k stride-s conv
into a ceil(k/s) x ceil(k/s) unit-stride one over s*s*Cin channels —
Mosaic slices vectors at unit stride only.  Epilogue fuses bias +
activation + residual bypass (the paper's VMOV-on-writeback for
ResNet), then the optional maxpool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..common import (apply_activation, compiler_params, default_interpret,
                      pltpu, vmem_scratch)
from ...core.dataflow import Dataflow
from ...core.ir import pool_out

__all__ = ["conv2d_strips_pallas", "conv2d_virtual_pallas", "compute_cols"]


def compute_cols(OW: int) -> int:
    """Output columns the kernel computes: OW rounded up to the f32
    sublane tile, so each tap's (rows, cols, Cin) patch flattens to
    (rows * cols, Cin) without a relayout.  The extra columns read
    right-padding and are dropped before the epilogue; callers pad the
    maps to ``compute_cols(OW) + kw - 1`` columns."""
    return -(-OW // 8) * 8


def _implicit_gemm(x_ref, r0, w_ref, rows, OW, kh, kw, kpt):
    """Accumulate the (dy, dx) taps of a unit-stride implicit GEMM in f32.

    x_ref: (1, Hp, Wp, Cin) maps block; r0: the strip's first input row.
    Each tap is a ref load at a (dy, dx) offset.  Returns (rows, OW,
    kpt)."""
    Cin = x_ref.shape[-1]
    cols = compute_cols(OW)
    acc = jnp.zeros((rows * cols, kpt), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            patch = x_ref[0, pl.ds(r0 + dy, rows), pl.ds(dx, cols), :]
            acc += jax.lax.dot_general(
                patch.astype(jnp.float32).reshape(rows * cols, Cin),
                w_ref[dy, dx].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc.reshape(rows, cols, kpt)[:, :OW]


def _epilogue(acc, bias_ref, byp, activation, bypass_first):
    """Bias + activation + residual bypass, fused on writeback."""
    if bias_ref is not None:
        acc = acc + bias_ref[...].astype(jnp.float32)
    if byp is not None and bypass_first:       # ResNet: add, then ReLU
        acc = acc + byp.astype(jnp.float32)
    acc = apply_activation(acc, activation)
    if byp is not None and not bypass_first:
        acc = acc + byp.astype(jnp.float32)
    return acc


# --- materialized strips (paper-faithful baseline) ---------------------------------
def _body(x_ref, w_ref, *rest, out_rows, OW, kh, kw,
          activation, out_dtype, has_bias, has_bypass,
          bypass_first=False):
    refs = list(rest)
    bias_ref = refs.pop(0) if has_bias else None
    byp_ref = refs.pop(0) if has_bypass else None
    o_ref = refs.pop(0)

    acc = _implicit_gemm(x_ref, 0, w_ref, out_rows, OW, kh, kw,
                         o_ref.shape[-1])
    byp = byp_ref[0] if byp_ref is not None else None
    acc = _epilogue(acc, bias_ref, byp, activation, bypass_first)
    o_ref[0] = acc.astype(out_dtype)


def conv2d_strips_pallas(strips, w, *, out_rows: int, OW: int, kpt: int,
                         bias=None, activation: str | None = None,
                         bypass=None, bypass_first: bool = False,
                         out_dtype=None,
                         dataflow: Dataflow = Dataflow.MAPS_RESIDENT,
                         interpret: bool | None = None) -> jax.Array:
    """strips: (NS, in_rows, Wp, Cin) halo-augmented row strips already
    materialized in HBM; w: (kh, kw, Cin, Cout), unit stride (ops.py
    folds a stride into channels); bypass: (NS, out_rows, OW, Cout) or
    None.  Returns (NS, out_rows, OW, Cout)."""
    if interpret is None:
        interpret = default_interpret()
    NS, in_rows, Wp, Cin = strips.shape
    kh, kw, _, Cout = w.shape
    assert Cout % kpt == 0, (Cout, kpt)
    NK = Cout // kpt
    out_dtype = out_dtype or strips.dtype
    has_bias = bias is not None
    has_bypass = bypass is not None

    if dataflow is Dataflow.WEIGHTS_RESIDENT:
        grid = (NK, NS)                      # weight tile resident (Mloop)
        s_idx = lambda kt, st: (st, 0, 0, 0)
        w_idx = lambda kt, st: (0, 0, 0, kt)
        o_idx = lambda kt, st: (st, 0, 0, kt)
        b_idx = lambda kt, st: (0, kt)
    else:                                    # maps resident (Kloop)
        grid = (NS, NK)
        s_idx = lambda st, kt: (st, 0, 0, 0)
        w_idx = lambda st, kt: (0, 0, 0, kt)
        o_idx = lambda st, kt: (st, 0, 0, kt)
        b_idx = lambda st, kt: (0, kt)

    in_specs = [
        pl.BlockSpec((1, in_rows, Wp, Cin), s_idx),
        pl.BlockSpec((kh, kw, Cin, kpt), w_idx),
    ]
    operands = [strips, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, kpt), b_idx))
        operands.append(bias.reshape(1, Cout))
    if has_bypass:
        in_specs.append(pl.BlockSpec((1, out_rows, OW, kpt), o_idx))
        operands.append(bypass)

    body = functools.partial(
        _body, out_rows=out_rows, OW=OW, kh=kh, kw=kw,
        activation=activation, out_dtype=out_dtype, has_bias=has_bias,
        has_bypass=has_bypass, bypass_first=bypass_first)
    # Output tiles are disjoint across both grid dims: parallel semantics
    # let Mosaic double-buffer the streamed operand.
    assert Wp >= compute_cols(OW) + kw - 1, (Wp, OW, kw)
    return pl.pallas_call(
        body,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, out_rows, OW, kpt), o_idx),
        out_shape=jax.ShapeDtypeStruct((NS, out_rows, OW, Cout), out_dtype),
        interpret=interpret,
        compiler_params=compiler_params(("parallel", "parallel")),
    )(*operands)


# --- virtual strips (zero-copy) ----------------------------------------------------
def _virtual_body(*refs, n_prefetch, strip_axis, out_rows, OH, OW, kh, kw,
                  rows_c, pool, OWo, activation, out_dtype, has_bias,
                  has_bypass, bypass_first):
    refs = list(refs)
    rs_ref = refs.pop(0) if n_prefetch else None
    x_ref = refs.pop(0)
    w_ref = refs.pop(0)
    bias_ref = refs.pop(0) if has_bias else None
    byp_ref = refs.pop(0) if has_bypass else None
    o_ref = refs.pop(0)
    pool_ref = refs.pop(0) if pool is not None else None

    s = pl.program_id(strip_axis)
    if rs_ref is not None:                     # scalar-prefetched offsets
        r0 = rs_ref[s]
    else:                                      # affine: s * out_rows
        r0 = s * out_rows
    # The zero-copy gather: each tap reads this strip's input window
    # straight out of the VMEM-resident padded maps — no HBM
    # duplication ever existed.
    kpt = o_ref.shape[-1]
    acc = _implicit_gemm(x_ref, r0, w_ref, rows_c, OW, kh, kw, kpt)
    byp = byp_ref[0] if byp_ref is not None else None
    acc = _epilogue(acc, bias_ref, byp, activation, bypass_first)

    if pool is None:
        o_ref[0] = acc.astype(out_dtype)
        return

    # Fused pool epilogue.  This strip owns pool rows
    # [s*SR, (s+1)*SR); pool row p needs conv rows [p*ps - pp,
    # p*ps - pp + pw), so local conv row l is global row
    # s*out_rows - pp + l.  Rows outside [0, OH) are the pool's
    # padding (or bottom fill) — mask them with the op's identity
    # before reducing: -inf for max, 0 for avg (avgpool2d_ref divides
    # by the fixed window^2, counting pad as zeros, so a zero identity
    # reproduces it exactly).
    # Mosaic slices vectors at unit stride only, so the conv rows are
    # staged in f32 VMEM scratch, framed by the pool's padding, and each
    # window tap is read back with a strided ref load.  Strided loads
    # take one 128-lane tile at a time: the scratch holds the kernel
    # tile's channels in 128-lane chunks.
    pw, ps, pp, pop = pool
    SR = out_rows // ps
    ident = jnp.float32(0.0 if pop == "avg" else -jnp.inf)
    gr = (s * out_rows - pp
          + jax.lax.broadcasted_iota(jnp.int32, (rows_c, 1, 1), 0))
    acc = jnp.where((gr >= 0) & (gr < OH), acc, ident)
    pool_ref[...] = jnp.full(pool_ref.shape, ident, jnp.float32)
    for j in range(pool_ref.shape[0]):
        c0, c1 = j * 128, min(kpt, (j + 1) * 128)
        pool_ref[j, :, pl.ds(pp, OW), :c1 - c0] = acc[:, :, c0:c1]
        pooled = None
        for py in range(pw):
            for px in range(pw):
                tap = pool_ref[j, pl.ds(py, SR, ps), pl.ds(px, OWo, ps), :]
                if pooled is None:
                    pooled = tap
                elif pop == "avg":
                    pooled = pooled + tap
                else:
                    pooled = jnp.maximum(pooled, tap)
        if pop == "avg":
            pooled = pooled / jnp.float32(pw * pw)
        o_ref[0, :, :, c0:c1] = pooled[:, :, :c1 - c0].astype(out_dtype)


def conv2d_virtual_pallas(xp, w, *, out_rows: int, OH: int, OW: int,
                          kpt: int, n_strips: int, bias=None,
                          activation: str | None = None, bypass=None,
                          bypass_first: bool = False, out_dtype=None,
                          dataflow: Dataflow = Dataflow.MAPS_RESIDENT,
                          pool: tuple[int, int, int] | None = None,
                          row_starts=None,
                          interpret: bool | None = None) -> jax.Array:
    """Zero-copy row-strip conv: xp is the whole padded maps
    (B, Hp, Wp, Cin) — no strip duplication; strips are gathered
    in-kernel.  w: (kh, kw, Cin, Cout), unit stride (ops.py folds a
    stride into channels).  bypass: (B, n_strips*out_rows, OW, Cout) or
    None (not combinable with ``pool``).  pool: (window, stride, pad,
    op) max or avg pool fused after the epilogue.  row_starts: optional
    (n_strips,) int32 per-strip *input* row offsets, scalar-prefetched so the gather
    address is known before the body runs — for input-side offset
    tables an affine ``s * out_rows`` cannot express (e.g.
    irregular row subsampling).  Output strips stay uniform: strip s
    always writes output rows [s*SR, (s+1)*SR), and the pool row mask
    is likewise derived from s, so a custom table must keep that
    output mapping valid.  Returns (B, n_strips*SR, OWo, Cout) where
    (SR, OWo) are the per-strip output rows / width after the
    optional pool."""
    if interpret is None:
        interpret = default_interpret()
    B, Hp, Wp, Cin = xp.shape
    kh, kw, _, Cout = w.shape
    assert Cout % kpt == 0, (Cout, kpt)
    NK = Cout // kpt
    NS = n_strips
    out_dtype = out_dtype or xp.dtype
    has_bias = bias is not None
    has_bypass = bypass is not None

    if pool is None:
        rows_c, SR, OWo = out_rows, out_rows, OW
    else:
        pw, ps, pp, _ = pool
        assert not has_bypass, "fused pool is not combinable with bypass"
        assert out_rows % ps == 0, (out_rows, ps)
        rows_c = out_rows + pw - ps            # extra rows: overlapping windows
        SR = out_rows // ps
        OWo = pool_out(OW, pw, ps, pp)
        wpool = max(pp + OW, (OWo - 1) * ps + pw)
    scratch = []
    if pool is not None:
        scratch.append(vmem_scratch((-(-kpt // 128), rows_c, wpool, 128),
                                    jnp.float32))
    assert (NS - 1) * out_rows + rows_c - 1 + kh <= Hp, \
        "padded maps too short for the strip table"
    assert Wp >= compute_cols(OW) + kw - 1, (Wp, OW, kw)

    if dataflow is Dataflow.WEIGHTS_RESIDENT:
        grid = (NK, B, NS)                   # weight tile resident (Mloop)
        strip_axis = 2
        x_idx = lambda kt, b, st: (b, 0, 0, 0)
        w_idx = lambda kt, b, st: (0, 0, 0, kt)
        o_idx = lambda kt, b, st: (b, st, 0, kt)
        b_idx = lambda kt, b, st: (0, kt)
    else:                                    # maps resident (Kloop)
        grid = (B, NS, NK)
        strip_axis = 1
        x_idx = lambda b, st, kt: (b, 0, 0, 0)
        w_idx = lambda b, st, kt: (0, 0, 0, kt)
        o_idx = lambda b, st, kt: (b, st, 0, kt)
        b_idx = lambda b, st, kt: (0, kt)

    n_prefetch = 0
    if row_starts is not None:
        n_prefetch = 1
        # Index maps receive the prefetch ref as a trailing arg.
        wrap = lambda f: (lambda *a: f(*a[:3]))
        x_idx, w_idx, o_idx, b_idx = (wrap(f) for f in
                                      (x_idx, w_idx, o_idx, b_idx))

    in_specs = [
        pl.BlockSpec((1, Hp, Wp, Cin), x_idx),
        pl.BlockSpec((kh, kw, Cin, kpt), w_idx),
    ]
    operands = [xp, w]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, kpt), b_idx))
        operands.append(bias.reshape(1, Cout))
    if has_bypass:
        in_specs.append(pl.BlockSpec((1, out_rows, OW, kpt), o_idx))
        operands.append(bypass)
    out_spec = pl.BlockSpec((1, SR, OWo, kpt), o_idx)
    out_shape = jax.ShapeDtypeStruct((B, NS * SR, OWo, Cout), out_dtype)

    body = functools.partial(
        _virtual_body, n_prefetch=n_prefetch, strip_axis=strip_axis,
        out_rows=out_rows, OH=OH, OW=OW, kh=kh, kw=kw,
        rows_c=rows_c, pool=pool, OWo=OWo, activation=activation,
        out_dtype=out_dtype, has_bias=has_bias, has_bypass=has_bypass,
        bypass_first=bypass_first)
    # All three grid dims write disjoint output blocks with no carried
    # state — parallel semantics everywhere (Mosaic double-buffers).
    params = compiler_params(("parallel",) * 3)
    if n_prefetch:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch)
        return pl.pallas_call(body, grid_spec=grid_spec,
                              out_shape=out_shape, interpret=interpret,
                              compiler_params=params)(
                                  row_starts.astype(jnp.int32), *operands)
    return pl.pallas_call(body, grid=grid, in_specs=in_specs,
                          out_specs=out_spec, out_shape=out_shape,
                          scratch_shapes=scratch, interpret=interpret,
                          compiler_params=params)(*operands)
