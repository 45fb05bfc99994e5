"""Public conv2d wrapper: schedule lookup, strip-storage decision,
dispatch, and shape restore.

The default path is **zero-copy**: the padded maps go to the kernel
whole (blocked only on batch / output channels) and each output-row
strip is gathered *inside* the kernel with a dynamic slice — the halo
rows are re-fetched from VMEM, never duplicated in HBM.  The paper's
scheme — materializing halo-augmented strips in DRAM so Snowflake's
DMA engine can issue contiguous single-burst loads — survives as the
``strip_storage="materialized"`` baseline; on hardware with random
VMEM access the overlap-duplication-vs-refetch tradeoff is a compiler
decision (``core/tiling.py``), not a constraint.

``fuse_pool=(window, stride[, pad[, op]])`` fuses a following max or
avg pool into the kernel epilogue (AlexNet / ResNet stem conv→pool,
GoogLeNet-style avg downsampling; stride-2 convs fuse the same way —
the strip geometry already carries the conv stride), eliminating the
pool layer's HBM round trip; on the materialized/reference paths it
degrades gracefully to a separate reference pool with identical
numerics.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..common import resolve_impl
from ...core.dataflow import Dataflow, choose_conv_dataflow
from ...core.hw import TPU_V5E, HardwareModel
from ...core.ir import pool_out
from ...core.tiling import ConvTiling, select_conv_row_strips
from .kernel import (compute_cols, conv2d_strips_pallas,
                     conv2d_virtual_pallas)
from .ref import avgpool2d_ref, conv2d_ref, maxpool2d_ref

__all__ = ["conv2d"]


def _materialize_strips(xp, n_strips, out_rows, in_rows):
    """Gather halo-augmented row strips into one HBM array:
    (B, Hp, Wp, C) -> (B*NS, in_rows, Wp, C).  This duplicates
    ``overlap_frac`` of the maps off-chip — the Snowflake baseline the
    zero-copy path exists to kill; kept for ``strip_storage=
    "materialized"`` and the strip-storage benchmark."""
    B, Hp, Wp, C = xp.shape
    starts = jnp.arange(n_strips) * out_rows
    def one(start):
        return jax.lax.dynamic_slice(xp, (0, start, 0, 0),
                                     (B, in_rows, Wp, C))
    strips = jax.vmap(one)(starts)                   # (NS, B, in_rows, Wp, C)
    strips = jnp.moveaxis(strips, 1, 0)              # (B, NS, ...)
    return strips.reshape(B * n_strips, in_rows, Wp, C)


def _norm_pool(fuse_pool):
    """Normalize to (window, stride, pad, op): pad defaults to 0, op to
    "max" (matching core/ir.py's fused_pool meta)."""
    if fuse_pool is None:
        return None
    fp = tuple(fuse_pool)
    if len(fp) == 2:
        fp = fp + (0,)
    if len(fp) == 3:
        fp = fp + ("max",)
    if fp[3] not in ("max", "avg"):
        raise ValueError(f"fuse_pool op must be max|avg, got {fp[3]!r}")
    return fp


def _pool_ref(out, pool):
    """The separate-pool fallback (reference / materialized / bypass
    paths) — identical numerics to the fused epilogue."""
    pw, ps, pp, op = pool
    ref = avgpool2d_ref if op == "avg" else maxpool2d_ref
    return ref(out, window=pw, stride=ps, pad=pp)


def conv2d(x, w, *, stride: int = 1, pad: int = 0, bias=None,
           activation: str | None = None, bypass=None,
           bypass_first: bool = False, out_dtype=None,
           impl: str = "auto", dataflow: Dataflow | None = None,
           hw: HardwareModel = TPU_V5E,
           strip_storage: str = "auto",
           fuse_pool: tuple[int, ...] | None = None,
           strip_offsets: str = "affine",
           tiling: ConvTiling | None = None,
           interpret: bool | None = None) -> jax.Array:
    """x: (B, H, W, Cin); w: (kh, kw, Cin, Cout); bypass broadcastable to
    the conv output (B, OH, OW, Cout).

    strip_storage: "auto" (tiler's VMEM-residency decision) |
    "virtual" (zero-copy in-kernel gather) | "materialized" (HBM halo
    duplication, paper-faithful).  fuse_pool: (window, stride[, pad[,
    op]]) max/avg pool fused into the epilogue (virtual path; other
    paths apply an equivalent reference pool).  strip_offsets: "affine" derives strip
    row offsets from the program id; "prefetch" routes them through a
    scalar-prefetched offset table instead.  tiling: a pre-resolved
    ``ConvTiling`` (the schedule's exact decision, as carried by a
    ``core/program.py`` op) — when given, no tiling is re-derived here.
    """
    if strip_storage not in ("auto", "virtual", "materialized"):
        raise ValueError(f"strip_storage must be auto|virtual|materialized, "
                         f"got {strip_storage!r}")
    if strip_offsets not in ("affine", "prefetch"):
        raise ValueError(f"strip_offsets must be affine|prefetch, "
                         f"got {strip_offsets!r}")
    pool = _norm_pool(fuse_pool)
    impl = resolve_impl(impl)
    if impl == "reference":
        out = conv2d_ref(x, w, stride=stride, pad=pad, bias=bias,
                         activation=activation, bypass=bypass,
                         bypass_first=bypass_first, out_dtype=out_dtype)
        if pool is not None:
            out = _pool_ref(out, pool)
        return out

    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    OH = (H + 2 * pad - kh) // stride + 1
    OW = (W + 2 * pad - kw) // stride + 1
    ct = tiling if tiling is not None else select_conv_row_strips(
        H, W, Cin, Cout, kh, kw, stride, pad, x.dtype.itemsize, hw, batch=B)
    storage = ct.strip_storage if strip_storage == "auto" else strip_storage
    out_rows = ct.out_rows
    kpt = _lane_kernel_tile(ct.kernels_per_tile, Cout)

    if storage != "virtual":
        # Paper-faithful fallback: conv via materialized strips, pool
        # (if requested) as a separate reference op.
        out = _conv2d_materialized(
            x, w, stride=stride, pad=pad, bias=bias, activation=activation,
            bypass=bypass, bypass_first=bypass_first, out_dtype=out_dtype,
            dataflow=dataflow, ct=ct, out_rows=out_rows, kpt=kpt,
            OH=OH, OW=OW, interpret=interpret)
        if pool is not None:
            out = _pool_ref(out, pool)
        return out

    if pool is not None and bypass is not None:
        # The fused-pool epilogue cannot also fold a residual add; do
        # the conv (with bypass) zero-copy and pool separately.
        out = conv2d(x, w, stride=stride, pad=pad, bias=bias,
                     activation=activation, bypass=bypass,
                     bypass_first=bypass_first, out_dtype=out_dtype,
                     impl=impl, dataflow=dataflow, hw=hw,
                     strip_storage="virtual", tiling=tiling,
                     strip_offsets=strip_offsets, interpret=interpret)
        return _pool_ref(out, pool)

    # --- zero-copy path ------------------------------------------------------
    top_pad = pad
    if pool is None:
        rows_c, SR, OHo, OWo = out_rows, out_rows, OH, OW
        n_strips = math.ceil(OH / out_rows)
    else:
        pw, ps, pp, _ = pool
        out_rows = max(ps, (out_rows // ps) * ps)   # strips own whole windows
        rows_c = out_rows + pw - ps
        SR = out_rows // ps
        OHo = pool_out(OH, pw, ps, pp)
        OWo = pool_out(OW, pw, ps, pp)
        if OHo < 1 or OWo < 1:
            raise ValueError(
                f"fuse_pool window {pw} (pad {pp}) does not fit the "
                f"{OH}x{OW} conv output")
        n_strips = math.ceil(OHo / SR)
        top_pad = pad + pp * stride      # phantom rows for the pool's top pad
    xs, ws = _fold_stride(x, w, stride, top=top_pad, left=pad,
                          rows=(n_strips - 1) * out_rows + rows_c,
                          OW=OW)

    if dataflow is None:
        by = x.dtype.itemsize
        out_bytes = B * OHo * OWo * Cout * by
        dataflow, _, _ = choose_conv_dataflow(
            B * H * W * Cin * by, Cin * kh * kw * Cout * by, out_bytes,
            n_map_tiles=B * n_strips, n_kernel_tiles=Cout // kpt,
            overlap_frac=ct.overlap_frac, strip_storage="virtual")

    byp = None
    if bypass is not None:
        byp = jnp.broadcast_to(bypass, (B, OH, OW, Cout))
        byp = jnp.pad(byp, ((0, 0), (0, n_strips * out_rows - OH),
                            (0, 0), (0, 0)))

    row_starts = None
    if strip_offsets == "prefetch":
        row_starts = jnp.arange(n_strips, dtype=jnp.int32) * out_rows

    out = conv2d_virtual_pallas(
        xs, ws, out_rows=out_rows, OH=OH, OW=OW, kpt=kpt,
        n_strips=n_strips, bias=bias, activation=activation, bypass=byp,
        bypass_first=bypass_first, out_dtype=out_dtype or x.dtype,
        dataflow=dataflow, pool=pool, row_starts=row_starts,
        interpret=interpret)
    return out[:, :OHo]


def _lane_kernel_tile(kpt: int, Cout: int) -> int:
    """The kernel-tile width the kernel runs: the widest divisor of Cout
    no wider than the schedule's ``kpt`` that fills whole 128-lane
    vregs, or all of Cout when none does (the TPU lowering accepts a
    channel block only at a lane multiple or the full extent)."""
    for t in range(kpt - kpt % 128, 0, -128):
        if Cout % t == 0:
            return t
    return Cout


def _fold_stride(x, w, stride, *, top, left, rows, OW):
    """Pad the maps and fold the conv stride into channels
    (space-to-depth), so the kernel runs a unit-stride conv.

    x: (B, H, W, C); w: (kh, kw, C, Co).  ``top`` / ``left`` rows and
    columns of zeros go before the maps; ``rows`` output rows (counted
    from the first padded one) must be computable, and each of them
    ``compute_cols(OW)`` sublane-aligned columns.  Stride-s row r of the
    padded maps becomes row r // s, channel block (r % s, col % s) of
    the folded maps; a k x k kernel becomes ceil(k/s) x ceil(k/s) taps
    over s*s*C channels, zero where k falls short of a multiple of s.
    Returns the folded maps (B, Hs, Ws, s*s*C) and weights."""
    B, H, W, C = x.shape
    kh, kw, _, Co = w.shape
    qh, qw = -(-kh // stride), -(-kw // stride)
    Hs = rows - 1 + qh
    Ws = compute_cols(OW) + qw - 1
    # lax.pad takes negative high padding: rows no output reads are cut.
    zero = jnp.zeros((), x.dtype)
    xp = jax.lax.pad(x, zero, ((0, 0, 0), (top, Hs * stride - H - top, 0),
                               (left, Ws * stride - W - left, 0),
                               (0, 0, 0)))
    if stride == 1:
        return xp, w
    xs = xp.reshape(B, Hs, stride, Ws, stride, C)
    xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(B, Hs, Ws, stride ** 2 * C)
    wp = jnp.pad(w, ((0, qh * stride - kh), (0, qw * stride - kw),
                     (0, 0), (0, 0)))
    ws = wp.reshape(qh, stride, qw, stride, C, Co)
    ws = ws.transpose(0, 2, 1, 3, 4, 5).reshape(qh, qw, stride ** 2 * C, Co)
    return xs, ws


def _conv2d_materialized(x, w, *, stride, pad, bias, activation, bypass,
                         bypass_first, out_dtype, dataflow, ct, out_rows,
                         kpt, OH, OW, interpret):
    """The paper's scheme: halo-augmented strips duplicated in HBM."""
    B, H, W, Cin = x.shape
    kh, kw, _, Cout = w.shape
    n_strips = math.ceil(OH / out_rows)

    if dataflow is None:
        by = x.dtype.itemsize
        dataflow, _, _ = choose_conv_dataflow(
            B * H * W * Cin * by, Cin * kh * kw * Cout * by,
            B * OH * OW * Cout * by,
            n_map_tiles=B * n_strips, n_kernel_tiles=Cout // kpt,
            overlap_frac=ct.overlap_frac, strip_storage="materialized")

    # Pad (+ bottom rows so every strip is full) and fold the stride.
    xs, ws = _fold_stride(x, w, stride, top=pad, left=pad,
                          rows=n_strips * out_rows, OW=OW)
    in_rows = out_rows - 1 + ws.shape[0]     # full window (pad supplies halo)
    strips = _materialize_strips(xs, n_strips, out_rows, in_rows)

    byp = None
    if bypass is not None:
        byp = jnp.broadcast_to(bypass, (B, OH, OW, Cout))
        pad_oh = n_strips * out_rows - OH
        byp = jnp.pad(byp, ((0, 0), (0, pad_oh), (0, 0), (0, 0)))
        byp = byp.reshape(B * n_strips, out_rows, OW, Cout)

    out = conv2d_strips_pallas(
        strips, ws, out_rows=out_rows, OW=OW, kpt=kpt,
        bias=bias, activation=activation, bypass=byp,
        bypass_first=bypass_first, out_dtype=out_dtype or x.dtype,
        dataflow=dataflow, interpret=interpret)
    out = out.reshape(B, n_strips * out_rows, OW, Cout)
    return out[:, :OH]
