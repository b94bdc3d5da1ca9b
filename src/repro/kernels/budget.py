"""One source of truth for on-chip memory budgets and fit arithmetic.

Every schedule decision in the stack — the ELL (``kernels/sparse_conv``)
and BCSR (``kernels/bsr_conv``) conv wrappers, the autotuner's candidate
pruning (``tuning/space.py``), and the pre-flight static verifier
(``repro.analysis``) — must agree on two things: how much VMEM/SMEM a
schedule's working set occupies, and how much the hardware offers.  Those
formulas used to be split between the two kernel ``ops.py`` modules (with
the budget constants re-declared in two more); this module is the single
home for both, so a budget change (new chip generation, different Mosaic
headroom) or a working-set term (a new scratch buffer) lands in exactly
one place.

The fit helpers take the budget as an explicit parameter defaulting to the
canonical constants — the kernel wrappers pass their own (monkeypatchable)
module aliases through, which keeps the historical test seams
(``monkeypatch.setattr(ops, "_VMEM_BUDGET", ...)``) working while the
arithmetic itself lives here.
"""
from __future__ import annotations

from repro.kernels.window import round_up, stage_shape, sublane_tile

# v5e has 128 MiB of VMEM per TensorCore, but Mosaic gives one kernel a
# scoped 16 MiB by default ("Ran out of memory in memory space vmem while
# allocating on stack" past it).  The budget leaves 4 MiB of that to
# Mosaic's own scratch and to the kernels' in-register temporaries.  Every
# working-set formula below counts each VMEM buffer at its (sublane, 128)
# tile-padded size, and each blocked operand twice — Pallas double-buffers
# blocked operands across grid steps.
VMEM_BUDGET = 12 * 1024 * 1024
# v5e has 1 MiB of SMEM per program ("Ran out of memory in memory space
# smem. Used 1.00M of 1.00M"), which holds the scalar-prefetched operands
# and the SMEM-blocked index/value tiles; 16 KiB stays with the compiler's
# own scalars.  SMEM rows are padded to 512 bytes.
SMEM_BYTES = 1024 * 1024
SMEM_BUDGET = SMEM_BYTES - 16 * 1024


def vmem_tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of one (rows, cols) VMEM plane at its tile-padded size: lanes
    pad to 128, sublanes to 8 rows of 32-bit words (16 for 2-byte, 32 for
    1-byte types)."""
    return (round_up(rows, sublane_tile(itemsize)) * round_up(cols, 128)
            * itemsize)


def smem_array_bytes(rows: int, cols: int, itemsize: int) -> int:
    """Bytes of one (rows, cols) SMEM array: each row pads to 512 bytes."""
    return rows * round_up(cols * itemsize, 512)


def channel_tile_ok(m: int, tm: int) -> bool:
    """A channel tile the TPU can block: it divides M and is a multiple of
    8 (the (8, 128) block rule on the SMEM index/value tiles) or all of
    M."""
    return 1 <= tm <= m and m % tm == 0 and (tm % 8 == 0 or tm == m)


def min_channel_tile(m: int) -> int:
    """The smallest legal channel tile (see :func:`channel_tile_ok`)."""
    return 8 if m % 8 == 0 else m


# Storage width (bytes) of each supported sparse-value dtype.  The quantised
# dtypes (int8 / fp8) store one byte per nonzero plus a per-output-channel
# f32 scale row accounted separately (SMEM for ELL, VMEM for BCSR).
VALUE_ITEMSIZES = {
    "float32": 4,
    "bfloat16": 2,
    "float16": 2,
    "int8": 1,
    "float8_e4m3fn": 1,
}


def value_itemsize(dtype: str) -> int:
    """Bytes per stored sparse value for ``dtype`` (a dtype name string)."""
    try:
        return VALUE_ITEMSIZES[dtype]
    except KeyError:
        raise ValueError(
            f"unknown sparse value dtype {dtype!r}; expected one of "
            f"{sorted(VALUE_ITEMSIZES)}") from None


# -- ELL direct sparse conv (kernels/sparse_conv) ---------------------------

def ell_smem_bytes(m: int, k: int, quantized: bool = False, *,
                   tm: int = None, value_itemsize: int = None) -> int:
    """SMEM footprint of the ELL kernel at channel tile ``tm`` (default: the
    smallest legal one): the (TM, K) int32 packed-index tile and the
    (TM, K) value tile, each double-buffered, plus the scalar-prefetched
    int32 nnz row and f32 bias row (M*4 each) and — for a quantised bank —
    the f32 per-channel scale row.  ``value_itemsize`` defaults to 1 for a
    quantised bank and 4 otherwise."""
    tm = min_channel_tile(m) if tm is None else tm
    vsize = value_itemsize or (1 if quantized else 4)
    tiles = 2 * (smem_array_bytes(tm, k, 4) + smem_array_bytes(tm, k, vsize))
    rows = (4 if quantized else 3) * smem_array_bytes(1, m, 4)
    return tiles + rows


def smem_fits(m: int, k: int, quantized: bool = False, *,
              tm: int = None, value_itemsize: int = None,
              smem_budget: int = None) -> bool:
    """The ELL kernel's SMEM operands fit at channel tile ``tm`` (default:
    the smallest legal tile — whether *any* tiling can fit); omitting the
    nnz row used to let index-heavy layers overshoot."""
    budget = SMEM_BUDGET if smem_budget is None else smem_budget
    return ell_smem_bytes(m, k, quantized, tm=tm,
                          value_itemsize=value_itemsize) <= budget


def ell_vmem_bytes(c: int, f: int, r: int, s: int, stride: int, tm: int,
                   te: int, fuse_res: bool = False,
                   pipeline: bool = False) -> int:
    """VMEM working set of one ELL (tm, te) tiling over F output columns:
    the staged f32 halo block (``window.stage_shape``; two with
    ``pipeline=True``, the double-buffered halo DMA schedule), the
    double-buffered f32 (TM, TE, F) out tile, and — when the fused epilogue
    accumulates a shortcut — the double-buffered residual tile.  Indices
    and values live in SMEM (:func:`ell_smem_bytes`)."""
    planes, rows, cols = stage_shape(c, r, s, stride, te, f)
    x_bytes = planes * vmem_tile_bytes(rows, cols, 4)
    if pipeline:
        x_bytes *= 2
    out_bytes = 2 * tm * vmem_tile_bytes(te, f, 4)
    res_bytes = out_bytes if fuse_res else 0
    return x_bytes + out_bytes + res_bytes


def ell_tiling_ok(m: int, e: int, tm: int, te: int) -> bool:
    """The TPU can block this ELL tiling: a legal channel tile and a row
    tile that is a multiple of 8 or all of E."""
    return channel_tile_ok(m, tm) and 1 <= te and (te >= e or te % 8 == 0)


def tiling_fits(m: int, c: int, e: int, f: int, k: int, r: int, s: int,
                stride: int, tm: int, te: int,
                fuse_res: bool = False, pipeline: bool = False,
                *, value_itemsize: int = 4, vmem_budget: int = None,
                smem_budget: int = None) -> bool:
    """Whether one ELL (tm, te) tiling is blockable and its working set
    fits both VMEM and SMEM."""
    if not ell_tiling_ok(m, e, tm, te):
        return False
    if not smem_fits(m, k, value_itemsize == 1, tm=tm,
                     value_itemsize=value_itemsize, smem_budget=smem_budget):
        return False
    budget = VMEM_BUDGET if vmem_budget is None else vmem_budget
    return ell_vmem_bytes(c, f, r, s, stride, tm, min(te, e),
                          fuse_res=fuse_res, pipeline=pipeline) <= budget


# -- BCSR MXU conv (kernels/bsr_conv) ---------------------------------------

def bsr_smem_bytes(gbm: int, kb: int) -> int:
    """SMEM footprint of the BCSR kernel's scalar-prefetched operands: the
    int32 block-column table (gbm*KB) and the int32 nblocks row (gbm)."""
    return smem_array_bytes(gbm, kb, 4) + smem_array_bytes(1, gbm, 4)


def bsr_smem_fits(gbm: int, kb: int, *, smem_budget: int = None) -> bool:
    """Both scalar-prefetched BCSR operands fit the SMEM budget."""
    budget = SMEM_BUDGET if smem_budget is None else smem_budget
    return bsr_smem_bytes(gbm, kb) <= budget


def bsr_vmem_bytes(c: int, r: int, s: int, stride: int, bm: int, bn: int,
                   te: int, f: int, itemsize: int = 4,
                   fuse_res: bool = False,
                   value_itemsize: int = None,
                   quantized: bool = False) -> int:
    """VMEM working set of one BCSR row tiling over F output columns: the
    staged halo block (``window.stage_shape``), the
    (bn, TE, F) patch scratch and its flattened f32 (bn, TE*F) contraction
    operand, the double-buffered (bm, bn) weight tile, the double-buffered
    f32 (bm, TE*F) out tile (and residual tile when fused), and the
    double-buffered (bm, 1) bias — plus a scale tile for a quantised bank.
    ``value_itemsize`` prices the weight tile at its storage width
    (defaults to the input ``itemsize``)."""
    planes, rows, cols = stage_shape(c, r, s, stride, te, f, itemsize)
    x_bytes = planes * vmem_tile_bytes(rows, cols, itemsize)
    patch_bytes = (bn * vmem_tile_bytes(te, f, itemsize)
                   + vmem_tile_bytes(bn, te * f, 4))
    vsize = itemsize if value_itemsize is None else value_itemsize
    w_bytes = 2 * vmem_tile_bytes(bm, bn, vsize)
    out_bytes = 2 * vmem_tile_bytes(bm, te * f, 4)
    res_bytes = 2 * vmem_tile_bytes(bm, te * f, itemsize) if fuse_res else 0
    row_bytes = (2 if quantized else 1) * 2 * vmem_tile_bytes(bm, 1, 4)
    return (x_bytes + patch_bytes + w_bytes + out_bytes + res_bytes
            + row_bytes)


def bsr_tiling_ok(e: int, f: int, te: int) -> bool:
    """The TPU can block this BCSR row tiling: the flat (bm, TE*F) out
    block is a multiple of 128 lanes or all of E*F."""
    return 1 <= te and (te >= e or (te * f) % 128 == 0)


def bsr_tiling_fits(c: int, r: int, s: int, stride: int, bm: int, bn: int,
                    te: int, f: int, itemsize: int = 4,
                    fuse_res: bool = False, *,
                    value_itemsize: int = None, quantized: bool = False,
                    vmem_budget: int = None) -> bool:
    """Whether one BCSR row tiling's working set fits VMEM (blockability is
    :func:`bsr_tiling_ok`)."""
    budget = VMEM_BUDGET if vmem_budget is None else vmem_budget
    return bsr_vmem_bytes(c, r, s, stride, bm, bn, te, f, itemsize=itemsize,
                          fuse_res=fuse_res, value_itemsize=value_itemsize,
                          quantized=quantized) <= budget
