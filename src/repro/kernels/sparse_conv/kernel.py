"""Pallas TPU kernel for Escoin's direct sparse convolution.

TPU adaptation of the paper's GPU kernel (Section 3.2/3.3):

  GPU thread block per output channel      -> grid cell per (image, spatial
                                              tile, channel tile)
  warp over consecutive ``w`` (coalescing) -> the (TE, F) output tile lives in
                                              VREG lanes; each nonzero issues
                                              one full-width FMA over the tile
  CSR value/colidx in shared memory        -> packed (c,r,s) indices and values
                                              in SMEM, one (TM, K) tile each
  inputs via read-only texture cache       -> the halo'd (planes, halo_h, W)
                                              input block for one row tile
                                              DMA'd HBM->VMEM once and reused by
                                              every nonzero of every channel
                                              tile of that cell
  partial sums in registers                -> float32 accumulator in VMEM out
                                              block
  rowptr loop bound                        -> fori_loop bounded by the true row
                                              nnz (padding entries never touched)

Spatial tiling: the grid is (N, ceil(E/TE), M/TM).  Each spatial cell — a
band of TE output rows — stages a *halo'd* input block of
``(TE-1)*stride + R`` rows by the full row width — overlapping blocks cannot
be expressed with blocked BlockSpecs, so the input stays in HBM
(``memory_space=HBM``) and the kernel issues an explicit sliced DMA into
VMEM scratch.  Tall feature maps run through the kernel as long as one
halo'd band fits the budget.

Double-buffered halo DMA pipeline (``pipeline=True``): the blocking schedule
staged each cell's block with ``start(); wait()`` back to back, so the VPU
idled for the entire HBM->VMEM copy of every spatial cell.  The pipelined
schedule allocates **two** halo scratch buffers with per-buffer DMA
semaphores and software-pipelines the grid: on the *last* channel tile of
spatial cell *i* the kernel resolves the (image, et) indices of cell
*i+1* from its linearised cell id and kicks off that cell's DMA into the
other buffer, so the copy flies while cell *i*'s remaining FMA work (and
cell *i+1*'s first channel tile's SMEM decode) executes.  Cell *i+1* then
only *waits* on its semaphore at ``mt == 0`` — by which point the copy has
had a full channel-tile loop to complete.  Buffers alternate by cell parity
(consecutive linear cells never share a slot), and the warm-up DMA for cell
0 is issued (then immediately waited) at the first grid step, which is the
one copy the pipeline cannot hide.  ``pipeline=False`` keeps the
single-buffer blocking schedule for tilings where doubling the halo block
would bust VMEM.

Mosaic layout: the staged block, the column phase split that makes every
strided window a static lane slice, and the per-nonzero window load live
in ``kernels/window.py``, shared with the BCSR kernel.  Scalars (packed
indices and values) live in SMEM — a vector memory cannot be read at a
dynamic lane — blocked per channel tile, so SMEM holds two (TM, K) tiles
of each rather than the whole bank.

Edge tiles: TE need not divide E.  The grid uses ceiling division; Pallas
drops out-of-range output writes, and the input is zero-padded so the last
tile's halo window stays in bounds (the extra zeros only ever feed
discarded output positions).  Block shapes follow the TPU's (8, 128) tile
rule: TE is a multiple of 8 or E, the tile spans all F columns, and TM is
a multiple of 8 or M (``ops.tile_candidates`` emits only such tilings).

Pointwise schedule (``tp`` set; 1x1, stride 1, no padding): every nonzero
multiplies one input channel at every pixel of every image, so the window
schedule's per-image (TE, F) tile — 49 of 1024 lanes at 7x7 — wastes the
vector unit and pays the per-nonzero loop once per image.  The entry point
instead lays the batch's pixels on lanes: the input goes to
``(C, rows, 128)`` (``N*E*F`` pixels, zero-padded to whole tiles), the grid
is (pixel tiles, M/TM) with channel tiles innermost, and each nonzero's FMA
covers a whole (TP, 128) slab read at a dynamic leading index.  The output
(and a fused residual) use the same layout and are transposed back.  Each
output element adds the same products in the same order as the window
schedule, so the two agree to the bit.

Index packing: each nonzero's (c, r, s) is packed into one int32 as
``c * (R*S) + r * S + s`` to keep the index stream at 4 bytes a nonzero; the
kernel decodes with two divmods (scalar ALU, off the critical VPU path).
This is exactly the paper's *weight stretching* trade-off: more index
arithmetic in exchange for fewer memory bytes.

Load balancing: the kernel itself is permutation-agnostic — feed it an
nnz-balanced bank (``core/sparse_format.py:balance_ell_conv``, rows sorted
by descending nnz) and each TM-tile's channel loop runs rows of
near-equal length instead of being bounded by its worst row; ``ops.py``
applies the inverse permutation to the output (and the forward permutation
to bias/residual) so callers never see the reordering.

Fused epilogue: the per-channel bias rides along as a scalar-prefetch
operand (f32 in SMEM, one scalar per output channel) and is added to the f32
accumulator before the single output write; a static ``fuse_relu`` flag
clamps the accumulator in-register, and an optional residual operand —
blocked exactly like the output tile — is accumulated for bottleneck tails
(``conv → bias → +shortcut → ReLU``).  Compared to the unfused executor this
removes two to three extra HBM round-trips of the full output tensor: the
accumulator leaves VMEM exactly once, epilogue applied.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.budget import pointwise_rows
from repro.kernels.window import load_window, phase_split, stage_shape


def _kernel(*refs,                       # scalar prefetch (SMEM), then the rest
            rs: int, s: int, stride: int, te: int, f: int, halo_h: int,
            fuse_relu: bool, has_res: bool, quantized: bool, pipeline: bool,
            et_n: int, n_cells: int, tm: int):
    # Scalar-prefetched operands lead: nnz row, bias row and — for a
    # quantised bank — the f32 per-channel scale row.  Then the HBM
    # phase-split input, the SMEM (TM, K) index and value tiles, the
    # optional residual tile, the output tile, and the scratch buffers.
    if quantized:
        nnz_ref, bias_ref, scale_ref, x_ref, idx_ref, val_ref, *rest = refs
    else:
        scale_ref = None
        nnz_ref, bias_ref, x_ref, idx_ref, val_ref, *rest = refs
    if has_res:
        res_ref, out_ref, xblk_ref, sem = rest
    else:
        res_ref = None
        out_ref, xblk_ref, sem = rest
    ni = pl.program_id(0)
    et = pl.program_id(1)
    mt = pl.program_id(2)
    mt_n = pl.num_programs(2)

    def src(ni_i, et_i):
        return x_ref.at[ni_i, :, pl.ds(et_i * te * stride, halo_h)]

    if pipeline:
        # Linearised spatial-cell id; buffers alternate by cell parity, so
        # the prefetch for cell i+1 never lands in the buffer cell i reads.
        cell = ni * et_n + et
        slot = lax.rem(cell, 2)

        def cell_dma(slot_i, ni_i, et_i):
            return pltpu.make_async_copy(
                src(ni_i, et_i), xblk_ref.at[slot_i], sem.at[slot_i])

        @pl.when(mt == 0)
        def _arrive():
            # Warm-up: cell 0 has no predecessor to prefetch it, so its
            # copy is issued here — the one DMA the pipeline cannot hide.
            @pl.when(cell == 0)
            def _warmup():
                cell_dma(slot, ni, et).start()
            # Every other cell's DMA was started on the predecessor's last
            # channel tile; the shape-matched descriptor waits it out.
            cell_dma(slot, ni, et).wait()

        @pl.when(jnp.logical_and(mt == mt_n - 1, cell + 1 < n_cells))
        def _prefetch():
            # Resolve the successor cell's (image, et) in-kernel from its
            # linear id and start its copy into the *other* buffer while
            # this cell's remaining FMA work computes.
            nxt = cell + 1
            cell_dma(lax.rem(nxt, 2), nxt // et_n, lax.rem(nxt, et_n)).start()
        lead = (slot,)
    else:
        # Blocking schedule: stage the halo'd block once per (image, spatial
        # tile); the channel-tile loop is the innermost grid dim, so the
        # block persists in scratch across every mt of this cell (TPU grids
        # run sequentially).
        @pl.when(mt == 0)
        def _stage():
            dma = pltpu.make_async_copy(src(ni, et), xblk_ref, sem)
            dma.start()
            dma.wait()
        lead = ()

    def load(ml, kk):
        packed = idx_ref[ml, kk]
        c = packed // rs
        rem = packed - c * rs
        r = rem // s
        ss = rem - r * s
        win = load_window(xblk_ref, lead, c, r, ss, s=s, stride=stride,
                          te=te, f=f)
        return win.astype(jnp.float32)

    def store(ml, acc):
        out_ref[0, ml] = acc

    _channel_loop(
        tm=tm, mt=mt, nnz_ref=nnz_ref, bias_ref=bias_ref,
        scale_ref=scale_ref, val_ref=val_ref, load=load, shape=(te, f),
        res=(lambda ml: res_ref[0, ml]) if has_res else None, store=store,
        fuse_relu=fuse_relu)


def _channel_loop(*, tm, mt, nnz_ref, bias_ref, scale_ref, val_ref, load,
                  shape, res, store, fuse_relu):
    """The per-channel loop both schedules share: for each output channel
    of the tile, one f32 FMA over the slab ``load(ml, kk)`` per true
    nonzero, in bank order, then the fused epilogue and one write
    (``store``).  ``res(ml)`` reads the channel's shortcut slab."""

    def channel(ml, _):
        m = mt * tm + ml

        def body(kk, acc):
            v = val_ref[ml, kk].astype(jnp.float32)
            if scale_ref is not None:
                # Dequantise at the FMA: multiply the int8/fp8 value by its
                # row's f32 scale *before* the slab product — the exact
                # multiply ``dequantize`` performs host-side, so this kernel
                # is bit-identical to the f32 kernel on a dequantised bank.
                v = v * scale_ref[m]
            return acc + v * load(ml, kk)

        acc0 = jnp.zeros(shape, dtype=jnp.float32)
        # CSR semantics: iterate only this row's true nonzeros.
        acc = lax.fori_loop(0, nnz_ref[m], body, acc0)
        # Fused epilogue on the in-register f32 accumulator: one output
        # write instead of separate bias / residual / ReLU HBM passes.
        acc = acc + bias_ref[m]
        if res is not None:
            acc = acc + res(ml).astype(jnp.float32)
        if fuse_relu:
            acc = jnp.maximum(acc, 0.0)
        store(ml, acc)
        return 0

    lax.fori_loop(0, tm, channel, 0)


def _pointwise_kernel(*refs, fuse_relu: bool, has_res: bool,
                      quantized: bool, tm: int):
    """The pointwise schedule (1x1, stride 1, no padding): the block holds
    every input channel at TP x 128 pixels of the flattened batch, so each
    nonzero's FMA covers a whole aligned (TP, 128) slab, read at a dynamic
    leading index ``x[c]`` — no halo, no window decode."""
    if quantized:
        nnz_ref, bias_ref, scale_ref, x_ref, idx_ref, val_ref, *rest = refs
    else:
        scale_ref = None
        nnz_ref, bias_ref, x_ref, idx_ref, val_ref, *rest = refs
    res_ref, out_ref = rest if has_res else (None, *rest)

    def load(ml, kk):
        # A 1x1 bank's packed index c*(R*S) + r*S + s is its channel c.
        return x_ref[idx_ref[ml, kk]]

    def store(ml, acc):
        out_ref[ml] = acc

    _channel_loop(
        tm=tm, mt=pl.program_id(1), nnz_ref=nnz_ref, bias_ref=bias_ref,
        scale_ref=scale_ref, val_ref=val_ref, load=load,
        shape=x_ref.shape[1:],
        res=(lambda ml: res_ref[ml]) if has_res else None, store=store,
        fuse_relu=fuse_relu)


def _to_lanes(a: jax.Array, rows: int) -> jax.Array:
    """(N, C, E, F) -> f32 (C, rows, 128): each channel's N*E*F pixels on
    rows of 128 lanes, zero-padded to ``rows``."""
    n, c, e, f = a.shape
    flat = jnp.transpose(a, (1, 0, 2, 3)).reshape(c, n * e * f)
    flat = jnp.pad(flat.astype(jnp.float32),
                   ((0, 0), (0, rows * 128 - n * e * f)))
    return flat.reshape(c, rows, 128)


def _from_lanes(y: jax.Array, n: int, e: int, f: int) -> jax.Array:
    """(M, rows, 128) -> (N, M, E, F), the padding rows cropped."""
    m = y.shape[0]
    y = y.reshape(m, -1)[:, :n * e * f].reshape(m, n, e, f)
    return jnp.transpose(y, (1, 0, 2, 3))


def _pointwise(x, value, packed_idx, nnz, bias, residual, scale, *, tm, k,
               tp, fuse_relu, interpret):
    """Launch the pointwise schedule: grid (pixel tiles, M/TM), channel
    tiles innermost, so the (C, TP, 128) input block — its index map
    ignores the channel tile — is fetched once per pixel tile."""
    n, c, e, f = x.shape
    m = value.shape[0]
    tiles = pl.cdiv(pointwise_rows(n, e, f), tp)
    rows = tiles * tp
    has_res = residual is not None
    quantized = scale is not None
    smem_tile = pl.BlockSpec((tm, k), lambda p, mt, *_: (mt, 0),
                             memory_space=pltpu.SMEM)
    slab = pl.BlockSpec((tm, tp, 128), lambda p, mt, *_: (mt, p, 0))
    in_specs = [pl.BlockSpec((c, tp, 128), lambda p, mt, *_: (0, p, 0)),
                smem_tile, smem_tile]
    prefetch = [nnz, bias, scale] if quantized else [nnz, bias]
    inputs = [*prefetch, _to_lanes(x, rows), packed_idx, value]
    if has_res:
        in_specs.append(slab)
        inputs.append(_to_lanes(residual, rows))
    y = pl.pallas_call(
        functools.partial(_pointwise_kernel, fuse_relu=fuse_relu,
                          has_res=has_res, quantized=quantized, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(tiles, m // tm),
            in_specs=in_specs,
            out_specs=slab,
        ),
        out_shape=jax.ShapeDtypeStruct((m, rows, 128), jnp.float32),
        interpret=interpret,
    )(*inputs)
    return _from_lanes(y, n, e, f)


@functools.partial(
    jax.jit,
    static_argnames=("tm", "k", "rs", "s", "e", "f", "stride", "te",
                     "fuse_relu", "pipeline", "tp", "interpret"))
def sparse_conv_pallas(xpad: jax.Array, value: jax.Array, packed_idx: jax.Array,
                       nnz: jax.Array, bias: jax.Array,
                       residual: jax.Array | None = None,
                       scale: jax.Array | None = None, *, tm: int, k: int,
                       rs: int, s: int, e: int, f: int, stride: int = 1,
                       te: int | None = None,
                       fuse_relu: bool = False, pipeline: bool = False,
                       tp: int | None = None,
                       interpret: bool = False) -> jax.Array:
    """Launch the direct sparse conv kernel: the spatially-tiled window
    schedule, or with ``tp`` the pointwise schedule.

    Args:
      xpad:       (N, C, Hp, Wp) pre-padded input (the paper's pad_in step).
      value:      (M, K) ELL values — f32, or int8/fp8 for a quantised bank
                  (``scale`` required; dequantised in-register at the FMA).
      packed_idx: (M, K) int32, c*(R*S) + r*S + s.
      nnz:        (M,) int32 true row lengths.
      bias:       (M,) f32 per-channel bias, added to the f32 accumulator
                  in-kernel (pass zeros for a bias-free conv — the add is
                  then a bitwise no-op).
      residual:   optional (N, M, E, F) shortcut accumulated before the ReLU
                  (bottleneck tail), blocked like the output tile.
      scale:      optional (M,) f32 per-output-channel quantisation scales,
                  scalar-prefetched beside the bias; each value is
                  multiplied by its row's scale before the window product,
                  so accumulation stays f32 throughout.
      tm:         output-channel tile; must divide M.
      e, f:       output spatial dims ((Hp - R) // stride + 1 etc.).
      stride:     conv stride (>= 1).
      te:         output row tile (default: all E rows, the untiled
                  schedule).  Need not divide E — edge tiles are handled by
                  a ceiling-division grid + masked writes.  Columns are
                  never tiled (Mosaic stages whole lane rows).
      fuse_relu:  clamp the accumulator in-kernel (the fused epilogue).
      pipeline:   double-buffer the halo DMA — two scratch buffers, the copy
                  for spatial cell i+1 issued while cell i computes — at the
                  cost of a second halo-block's VMEM.  False keeps the
                  single-buffer blocking schedule.
      tp:         pixel tile of the pointwise schedule, in rows of 128
                  lanes (``budget.pointwise_tile``), for a 1x1, stride-1,
                  unpadded layer only; ``te`` and ``pipeline`` then do not
                  apply.

    Returns: (N, M, E, F) float32.
    """
    n, c, hp, wp = xpad.shape
    m = value.shape[0]
    if tm < 1 or m % tm:
        # A stale tuned plan (or caller typo) must surface loudly even under
        # ``python -O`` — an assert would vanish and the BlockSpecs would
        # silently mis-tile the channel axis.
        raise ValueError(
            f"channel tile tm={tm} does not divide M={m} "
            f"(geometry: n={n} c={c} hp={hp} wp={wp} k={k} rs={rs} "
            f"stride={stride} e={e} f={f})")
    if tp is not None:
        if rs != 1 or stride != 1 or (hp, wp) != (e, f):
            raise ValueError(
                f"the pointwise schedule runs 1x1, stride-1, unpadded layers "
                f"only (rs={rs} stride={stride} hp={hp} wp={wp} e={e} f={f})")
        return _pointwise(xpad, value, packed_idx, nnz, bias, residual,
                          scale, tm=tm, k=k, tp=tp, fuse_relu=fuse_relu,
                          interpret=interpret)
    te = e if te is None else min(te, e)
    r = rs // s
    planes, halo_h, wq = stage_shape(c, r, s, stride, te, f,
                                     xpad.dtype.itemsize)
    et_n = pl.cdiv(e, te)
    # Pad so the *last* tile's halo block stays in bounds; the extra
    # rows/cols only ever feed output positions >= E/F, which Pallas drops.
    xs = phase_split(xpad, s=s, stride=stride,
                     rows=(et_n - 1) * te * stride + halo_h, cols=wq)
    grid = (n, et_n, m // tm)
    has_res = residual is not None
    quantized = scale is not None
    smem_tile = pl.BlockSpec((tm, k), lambda ni, et, mt, *_: (mt, 0),
                             memory_space=pltpu.SMEM)
    in_specs = [pl.BlockSpec(memory_space=pltpu.HBM), smem_tile, smem_tile]
    prefetch = [nnz, bias, scale] if quantized else [nnz, bias]
    inputs = [*prefetch, xs, packed_idx, value]
    if has_res:
        in_specs.append(pl.BlockSpec(
            (1, tm, te, f), lambda ni, et, mt, *_: (ni, mt, et, 0)))
        inputs.append(residual)
    halo = (planes, halo_h, wq)
    if pipeline:
        scratch = [pltpu.VMEM((2, *halo), xpad.dtype),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        scratch = [pltpu.VMEM(halo, xpad.dtype), pltpu.SemaphoreType.DMA]
    return pl.pallas_call(
        functools.partial(_kernel, rs=rs, s=s, stride=stride, te=te, f=f,
                          halo_h=halo_h, fuse_relu=fuse_relu,
                          has_res=has_res, quantized=quantized,
                          pipeline=pipeline, et_n=et_n, n_cells=n * et_n,
                          tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, tm, te, f), lambda ni, et, mt, *_: (ni, mt, et, 0)),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((n, m, e, f), jnp.float32),
        interpret=interpret,
    )(*inputs)
