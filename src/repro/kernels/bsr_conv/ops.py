"""Jit'd public wrapper around the BCSR MXU conv kernel.

Handles: input padding (pad_in), output row tile selection (te; the kernel
always computes whole output rows) with the halo'd-block VMEM
feasibility model and the TPU's block rule (``budget.bsr_tiling_ok``),
channel padding (the format blocks M up to gbm*bm — bias and residual are
padded in, the output sliced back),
the dtype policy (bf16/f32 in, f32 accumulate, cast back on exit), the fused
epilogue (bias / ReLU / bottleneck residual on the f32 accumulator,
one output write), and the fallback to the dense-reconstruction conv — with
the identical epilogue applied unfused — for geometries whose block table
busts the SMEM budget or for which no VMEM-feasible spatial tiling exists.

The block shape (bm, bn) is the format's, fixed at ``bcsr_conv_from_dense``
time; the wrapper's tunable axes are the spatial tiles, which the
``repro.tuning`` autotuner turns alongside the block-size candidates.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.direct_conv import out_spatial
from repro.core.sparse_format import BcsrConv, bcsr_conv_to_dense
from repro.kernels import budget
from repro.kernels.budget import SMEM_BUDGET, VMEM_BUDGET, value_itemsize
from repro.kernels.bsr_conv.kernel import bsr_conv_pallas
from repro.kernels.bsr_conv.ref import bsr_conv_ref
from repro.kernels.sparse_conv.ops import apply_epilogue, spatial_candidates
from repro.kernels.window import halo_extent
from repro.telemetry.fallback import record_fallback

# The candidate (bm, bn) block shapes the autotuner enumerates: bn pinned to
# the 128-lane MXU width, bm laddered — bigger bm amortises the per-block
# patch gather over more systolic rows (the gather-vs-compute tradeoff the
# roofline prices), smaller bm wastes less on channel padding.
BLOCK_CANDIDATES = ((8, 128), (16, 128), (32, 128), (64, 128))


def bsr_smem_fits(gbm: int, kb: int) -> bool:
    """Both scalar-prefetched operands fit SMEM: the int32 block-column
    table (gbm*KB) and the int32 nblocks row (gbm).  Formula lives in
    ``repro.kernels.budget``; the module-level ``SMEM_BUDGET`` alias is the
    (monkeypatchable) budget this wrapper passes through."""
    return budget.bsr_smem_fits(gbm, kb, smem_budget=SMEM_BUDGET)


def bsr_tiling_fits(c: int, r: int, s: int, stride: int, bm: int, bn: int,
                    te: int, f: int, itemsize: int = 4,
                    fuse_res: bool = False,
                    value_itemsize: Optional[int] = None,
                    quantized: bool = False) -> bool:
    """Whether one row tiling's working set over F output columns — halo'd
    input block + (bm, bn) weight tile + (bn, te, F) patch tile + f32 out tile (+ the
    residual input tile when fused, + the (bm, 1) f32 scale tile for a
    quantised bank) — fits the VMEM budget (``repro.kernels.budget``
    arithmetic, this module's budget alias).  ``value_itemsize`` prices
    the weight tile at its storage width (defaults to the input
    itemsize)."""
    return budget.bsr_tiling_fits(c, r, s, stride, bm, bn, te, f,
                                  itemsize=itemsize, fuse_res=fuse_res,
                                  value_itemsize=value_itemsize,
                                  quantized=quantized,
                                  vmem_budget=VMEM_BUDGET)


def bsr_tile_candidates(c: int, e: int, f: int, r: int, s: int, stride: int,
                        bm: int, bn: int, itemsize: int = 4,
                        fuse_res: bool = False,
                        value_itemsize: Optional[int] = None,
                        quantized: bool = False) -> List[int]:
    """All blockable row tiles ``te`` whose VMEM working set fits,
    preferred first (a tile always spans all F columns): fewest spatial
    cells (least halo re-fetch and least per-cell patch re-gather), then
    least total staged input traffic."""
    out = [te for te in spatial_candidates(e)
           if budget.bsr_tiling_ok(e, f, te)
           and bsr_tiling_fits(c, r, s, stride, bm, bn, te, f,
                               itemsize=itemsize, fuse_res=fuse_res,
                               value_itemsize=value_itemsize,
                               quantized=quantized)]

    def pref(te: int) -> Tuple[int, int]:
        cells = -(-e // te)
        return (cells, cells * halo_extent(te, stride, r))

    return sorted(out, key=pref)


def resolve_bsr_schedule(c: int, e: int, f: int, r: int, s: int, stride: int,
                         bm: int, bn: int, gbm: int, kb: int, *,
                         itemsize: int = 4, te: Optional[int] = None,
                         fuse_res: bool = False,
                         value_dtype: str = "float32",
                         ) -> Tuple[Optional[int], Optional[str]]:
    """The dispatch decision ``bsr_conv`` makes, as a pure function.

    Returns ``(te, None)`` for the row tile the MXU kernel
    would run, or ``(None, reason)`` — a ``telemetry.fallback`` reason
    code — when the layer falls back to the dense-reconstruction conv.
    The engine's ExecutionReport and the benchmark's zero-fallback
    invariant probe dispatch through this; ``bsr_conv`` runs it too.

    ``value_dtype`` names the bank's storage dtype: a quantised bank
    (int8 / float8_e4m3fn) shrinks the VMEM weight tile to one byte per
    element but streams an extra (1, bm) f32 scale tile — both accounted
    here so feasibility matches what the kernel would allocate.
    """
    vsize = value_itemsize(value_dtype)
    quantized = vsize == 1
    if not bsr_smem_fits(gbm, kb):
        return None, "smem_infeasible"
    if te is not None:
        # Pinned row tile (tuned plan / caller override): honor it when it
        # is blockable and fits, never launch an over-budget or
        # uncompilable kernel.
        te = min(te, e)
        if not (budget.bsr_tiling_ok(e, f, te)
                and bsr_tiling_fits(c, r, s, stride, bm, bn, te, f,
                                    itemsize=itemsize, fuse_res=fuse_res,
                                    value_itemsize=vsize,
                                    quantized=quantized)):
            return None, "no_feasible_tiling"
        return te, None
    cands = bsr_tile_candidates(c, e, f, r, s, stride, bm, bn,
                                itemsize=itemsize, fuse_res=fuse_res,
                                value_itemsize=vsize, quantized=quantized)
    if not cands:
        return None, "no_feasible_tiling"
    return cands[0], None


def bsr_conv(x: jax.Array, bc: BcsrConv, *, stride: int = 1,
             padding: int = 0, te: Optional[int] = None,
             bias: Optional[jax.Array] = None,
             fuse_relu: bool = False, residual: Optional[jax.Array] = None,
             interpret: bool = False,
             layer: Optional[str] = None) -> jax.Array:
    """Block-sparse convolution + fused epilogue on the MXU.

    (N, C, H, W) input, BCSR filter bank for (M, C, R, S) weights ->
    (N, M, E, F) in x.dtype.  Any stride >= 1 runs in-kernel; te defaults
    to the preferred feasible row tile and is the knob the
    ``repro.tuning`` autotuner turns (together with the format's block
    shape).  Falls back to the dense-reconstruction conv — with the
    identical epilogue applied unfused — when the block-column table busts
    SMEM or no spatial tiling fits VMEM, so ``bsr_conv`` is a complete
    conv+epilogue operator either way; any such fallback is reported
    through ``telemetry.record_fallback`` (one-time warning + gated
    counters), ``layer`` naming the conv op when the caller knows it.
    """
    m, c, r, s = bc.shape
    gbm, kb_dim, bm, bn = bc.blocks.shape
    n, _, h, w = x.shape
    e, f = out_spatial(h, w, r, s, stride, padding)
    fuse_res = residual is not None
    itemsize = jnp.dtype(x.dtype).itemsize

    def fallback(reason: str) -> jax.Array:
        record_fallback(
            "bsr_conv", reason, layer=layer,
            geometry=(f"m={m} c={c} e={e} f={f} bm={bm} bn={bn} gbm={gbm} "
                      f"kb={kb_dim} r={r} s={s} stride={stride}"),
            fallback_to="dense")
        y = bsr_conv_ref(x, bcsr_conv_to_dense(bc), stride=stride,
                         padding=padding).astype(x.dtype)
        return apply_epilogue(y, bias, fuse_relu, residual)

    sched, reason = resolve_bsr_schedule(c, e, f, r, s, stride, bm, bn,
                                         gbm, kb_dim, itemsize=itemsize,
                                         te=te, fuse_res=fuse_res,
                                         value_dtype=bc.value_dtype)
    if sched is None:
        return fallback(reason)
    te = sched
    xpad = jnp.pad(x, ((0, 0), (0, 0), (padding, padding), (padding, padding)))
    # Channel padding: the kernel computes gbm*bm output channels; bias and
    # residual are padded to match, the result sliced back to M.
    mpad = gbm * bm
    b = (jnp.zeros((m,), jnp.float32) if bias is None
         else jnp.asarray(bias, jnp.float32))
    b = jnp.pad(b, (0, mpad - m)).reshape(gbm, bm)
    res = residual
    if res is not None and mpad != m:
        res = jnp.pad(res, ((0, 0), (0, mpad - m), (0, 0), (0, 0)))
    out = bsr_conv_pallas(
        xpad, bc.blocks, bc.blockcol, bc.nblocks, b, res, scale=bc.scale,
        rs=r * s, s=s, e=e, f=f, stride=stride, te=te,
        fuse_relu=fuse_relu, interpret=interpret)
    return out[:, :m].astype(x.dtype)
