"""Pallas TPU kernel: block-sparse (BCSR) direct convolution on the MXU.

The ELL kernel (``kernels/sparse_conv``) issues one full-width VPU FMA per
nonzero weight — the faithful TPU port of Escoin's per-nonzero GPU threads.
That is the right shape for *very* sparse banks, but moderately-sparse,
large-channel layers (GoogLeNet 1x1s, ResNet bottlenecks) burn VPU issue
slots one scalar weight at a time while the 128x128 systolic array idles.
This kernel trades a little pruning flexibility for dense-unit throughput
(Park et al.'s direct sparse convolution refined with the Balanced-Sparsity
insight of block-structured pruning): weights are pruned at (bm, bn) tile
granularity over the flattened (M, C*R*S) weight matrix
(``core/sparse_format.py:BcsrConv``), surviving tiles stay fully dense, and
each one becomes a single MXU contraction against a gathered input-patch
tile.

Mechanics:

  * grid = (N, ceil(E/TE), gbm, KB) with KB innermost so the (bm, TE*F)
    f32 output block stays VMEM-resident and accumulates across the kept
    weight tiles of its block-row (the ``bsr_matmul`` accumulation
    pattern, tiled over output rows).  The output is laid out flat,
    (N, M, E*F), so the accumulator fills the 128 vector lanes whatever
    the feature map's width; the wrapper reshapes it back to NCHW.
  * the halo'd input block for one band of TE output rows is
    DMA'd HBM->VMEM once — at the cell's first (mt, kb) step — and reused
    by every weight tile of every block-row of that cell (the ELL kernel's
    staging discipline; overlapping halo blocks cannot be expressed with
    blocked BlockSpecs, so the input stays in ``ANY`` and the kernel issues
    an explicit sliced copy).
  * per kept tile, the *gather* stage decodes each of the tile's bn flat
    weight columns ``j = blockcol*bn + jl`` into ``(c, r, s)`` (two static
    divmods — the same index arithmetic weight stretching trades bytes for)
    and writes the strided (TE, F) input window into row ``jl`` of a
    (bn, TE, F) VMEM patch buffer: an im2col patch tile, built on-chip
    from the staged halo block instead of materialised in HBM (the
    bandwidth waste the paper's direct method exists to remove).
  * the *contract* stage is one 2-D matmul of the (bm, bn) weight tile
    against the patch tile flattened to (bn, TE*F), with f32 accumulation
    — MXU work (Mosaic contracts 2-D operands only).
    The gather is VPU work; the autotuner's roofline prices exactly this
    gather-vs-systolic tradeoff (``tuning/measure.py:_bsr_terms``).
  * rows shorter than KB mask the tail via ``pl.when`` on ``nblocks``;
    block-columns past C*R*S (format right-padding) clamp their channel
    decode — their weights are zero, so the clamped reads are inert.
  * the fused epilogue (per-channel bias, optional residual, static ReLU)
    runs on the resident f32 accumulator at the last KB step — one output
    write, exactly like the ELL kernel's epilogue.

Strides, windows and edge tiles follow the ELL kernel: the wrapper's
column phase split with a static-lane, sublane-strided window load per
decoded column (``kernels/window.py``), a ceiling-division
row grid with masked out-of-range writes, and input zero-padding so every
halo window stays in bounds.  Block shapes follow the TPU's (8, 128) tile
rule: the flat output block's TE*F is a multiple of 128 or covers E*F.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.window import load_window, phase_split, stage_shape


def _kernel(blockcol_ref, nblocks_ref,   # scalar prefetch (SMEM)
            x_ref,                       # HBM: phase-split padded input
            w_ref,                       # VMEM in: (1, 1, bm, bn)
            b_ref,                       # VMEM in: (1, bm, 1) f32 bias
            *rest,                       # [scale_ref,] [res_ref,] out_ref,
                                         # xblk, patch, sem
            bn: int, rs: int, s: int, c_in: int, stride: int, te: int,
            f: int, halo_h: int, fuse_relu: bool, has_res: bool,
            quantized: bool):
    rest = list(rest)
    scale_ref = rest.pop(0) if quantized else None
    if has_res:
        res_ref, out_ref, xblk_ref, patch_ref, sem = rest
    else:
        res_ref = None
        out_ref, xblk_ref, patch_ref, sem = rest
    ni = pl.program_id(0)
    et = pl.program_id(1)
    mt = pl.program_id(2)
    kb = pl.program_id(3)
    kb_n = pl.num_programs(3)

    # Stage the halo'd input band once per (image, row tile); the (mt, kb)
    # dims are innermost, so it persists for every weight tile of this
    # cell (TPU grids run sequentially).
    @pl.when(jnp.logical_and(mt == 0, kb == 0))
    def _stage():
        dma = pltpu.make_async_copy(
            x_ref.at[ni, :, pl.ds(et * te * stride, halo_h)], xblk_ref, sem)
        dma.start()
        dma.wait()

    @pl.when(kb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(kb < nblocks_ref[mt])
    def _accum():
        j0 = blockcol_ref[mt, kb] * bn

        # Gather (VPU): build the (bn, TE, F) im2col patch tile for this
        # block column from the staged halo block, one decoded weight
        # column per row.
        def gather(jl, _):
            j = j0 + jl
            cj = j // rs
            rem = j - cj * rs
            r = rem // s
            ss = rem - r * s
            # Right-padding columns (j >= C*R*S) carry zero weights; clamp
            # the channel so their gather stays in bounds (value is inert).
            cj = jnp.minimum(cj, c_in - 1)
            patch_ref[jl] = load_window(xblk_ref, (), cj, r, ss, s=s,
                                        stride=stride, te=te, f=f)
            return 0

        lax.fori_loop(0, bn, gather, 0)
        # Contract (MXU): one (bm, bn) x (bn, TE*F) systolic pass, f32
        # accumulate into the resident output block.
        patch = patch_ref[...].astype(jnp.float32).reshape(bn, te * f)
        contrib = jnp.dot(w_ref[0, 0].astype(jnp.float32), patch,
                          preferred_element_type=jnp.float32)
        if quantized:
            # Dequantise after the contraction: the int8/fp8 tile is
            # contracted as-is in f32 and each output row's contribution is
            # scaled by its per-channel f32 scale before accumulating —
            # accumulation stays f32 throughout.
            contrib = scale_ref[0] * contrib
        out_ref[0] += contrib

    # Fused epilogue on the resident f32 accumulator at the last KB step:
    # one output write instead of separate bias / residual / ReLU passes.
    @pl.when(kb == kb_n - 1)
    def _epilogue():
        acc = out_ref[0] + b_ref[0]
        if has_res:
            acc = acc + res_ref[0].astype(jnp.float32)
        if fuse_relu:
            acc = jnp.maximum(acc, 0.0)
        out_ref[0] = acc


@functools.partial(
    jax.jit,
    static_argnames=("rs", "s", "e", "f", "stride", "te", "fuse_relu",
                     "interpret"))
def bsr_conv_pallas(xpad: jax.Array, blocks: jax.Array, blockcol: jax.Array,
                    nblocks: jax.Array, bias: jax.Array,
                    residual: jax.Array | None = None,
                    scale: jax.Array | None = None, *, rs: int, s: int,
                    e: int, f: int, stride: int = 1, te: int | None = None,
                    fuse_relu: bool = False,
                    interpret: bool = False) -> jax.Array:
    """Launch the BCSR MXU conv kernel.

    Args:
      xpad:     (N, C, Hp, Wp) pre-padded input (the paper's pad_in step).
      blocks:   (gbm, KB, bm, bn) kept weight tiles (``BcsrConv.blocks``) —
                f32, or int8/fp8 for a quantised bank (``scale`` required).
      blockcol: (gbm, KB) int32 block-column ids over the flat C*R*S axis.
      nblocks:  (gbm,) int32 true tiles per block-row.
      bias:     (gbm, bm) f32 per-channel bias, blocked like the output
                channels (pass zeros for a bias-free conv — bitwise no-op).
      residual: optional (N, gbm*bm, E, F) shortcut accumulated before the
                ReLU, channel-padded like the output.
      scale:    optional (gbm, bm) f32 per-output-channel quantisation
                scales, blocked like the bias; each weight tile's post-MXU
                contribution is scaled by its rows' scales before the f32
                accumulate.
      rs, s:    R*S and S of the original filter bank (column decode).
      e, f:     output spatial dims.
      stride:   conv stride (>= 1).
      te:       output row tile (default: all E rows).  Need not divide E —
                edge tiles use a ceiling-division grid + masked writes;
                TE*F must be a multiple of 128 unless TE covers E.
                Columns are never tiled (Mosaic stages whole lane rows).
      fuse_relu: clamp the accumulator in-kernel (the fused epilogue).

    Returns: (N, gbm*bm, E, F) float32 — callers slice to the true M.
    """
    n, c, hp, wp = xpad.shape
    gbm, kb_dim, bm, bn = blocks.shape
    te = e if te is None else min(te, e)
    if te < e and (te * f) % 128:
        raise ValueError(f"row tile te={te} x F={f} is not a multiple of "
                         "128 lanes")
    r = rs // s
    planes, halo_h, wq = stage_shape(c, r, s, stride, te, f,
                                     xpad.dtype.itemsize)
    et_n = pl.cdiv(e, te)
    xs = phase_split(xpad, s=s, stride=stride,
                     rows=(et_n - 1) * te * stride + halo_h, cols=wq)
    grid = (n, et_n, gbm, kb_dim)
    has_res = residual is not None
    quantized = scale is not None
    row_spec = pl.BlockSpec((1, bm, 1), lambda ni, et, mt, kb, *_: (mt, 0, 0))
    flat_spec = pl.BlockSpec((1, bm, te * f),
                             lambda ni, et, mt, kb, *_: (ni, mt, et))
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec((1, 1, bm, bn), lambda ni, et, mt, kb, *_: (mt, kb, 0, 0)),
        row_spec,
    ]
    inputs = [blockcol, nblocks, xs, blocks, bias.reshape(gbm, bm, 1)]
    if quantized:
        in_specs.append(row_spec)
        inputs.append(scale.reshape(gbm, bm, 1))
    if has_res:
        in_specs.append(flat_spec)
        inputs.append(residual.reshape(n, gbm * bm, e * f))
    out = pl.pallas_call(
        functools.partial(_kernel, bn=bn, rs=rs, s=s, c_in=c, stride=stride,
                          te=te, f=f, halo_h=halo_h, fuse_relu=fuse_relu,
                          has_res=has_res, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=flat_spec,
            scratch_shapes=[
                pltpu.VMEM((planes, halo_h, wq), xpad.dtype),
                pltpu.VMEM((bn, te, f), xpad.dtype),
                pltpu.SemaphoreType.DMA,
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n, gbm * bm, e * f), jnp.float32),
        interpret=interpret,
    )(*inputs)
    return out.reshape(n, gbm * bm, e, f)
