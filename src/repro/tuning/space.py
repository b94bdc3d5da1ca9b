"""Candidate space for per-layer kernel customization (paper §3.3-3.4).

Escoin's speedups come from picking, per conv layer, the execution strategy
and tile shape that fit that layer's geometry and sparsity.  This module
enumerates the discrete choices the tuner measures over:

  method      ∈ {dense, lowered, csr-direct, pallas, bsr}  (paper Figs. 8-11
               columns, plus the beyond-paper BCSR MXU conv path)
  (tm,te)     ∈ output-channel x output-row tilings whose halo'd input
               block + out tile fit the VMEM budget (pallas only; te = None
               means the untiled full-extent schedule; a tile always spans
               all F columns)
  pad_to      ∈ ELL row-padding buckets (K granularity; trades padded work
               for jit-specialisation sharing)
  fuse        ∈ {False, True}  (pallas only): execute the conv's epilogue —
               bias add, ReLU, bottleneck shortcut — in-kernel on the f32
               accumulator (one output write) instead of as separate HBM
               passes.  Fused-residual candidates must additionally fit the
               shortcut input tile in VMEM.
  pipeline    ∈ {False, True}  (pallas only): double-buffer the halo DMA —
               stage spatial cell i+1's input block while cell i computes —
               at the cost of a second halo scratch block in VMEM.
               Pipelined candidates enumerate only tilings whose *doubled*
               halo block fits the budget.
  permute     ∈ {False, True}  (pallas only): run an nnz-balanced bank
               (output channels sorted by row nnz) so every TM-tile holds
               rows of near-equal length; costs an inverse-permutation
               gather of the output.
  (bm, bn)    ∈ BCSR block-shape candidates (bsr only): the tile
               granularity of the block-pruned weight matrix.  Bigger bm
               amortises the per-block patch gather over more systolic
               rows; smaller bm wastes less channel padding.  bsr
               candidates also carry te row tiles and the fuse
               axis, but no tm/pad_to/pipeline/permute — the block shape
               plays tm's role and the kernel's halo DMA is blocking.

Hardware-infeasible points are pruned statically: the Pallas kernel's packed
index array (+ the int32 nnz row + the f32 bias row) must fit the SMEM
budget, and every emitted tiling fits VMEM
(``kernels.sparse_conv.ops.tile_candidates`` /
``kernels.bsr_conv.ops.bsr_tile_candidates``).  Strided layers are eligible
— the kernels apply the stride in-kernel.  Fully-dense layers (sparsity ==
0) only ever run dense.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional, Tuple

from repro.core.sparse_format import ell_k
from repro.kernels.bsr_conv.ops import BLOCK_CANDIDATES, bsr_tile_candidates
from repro.kernels.budget import bsr_smem_fits, smem_fits, value_itemsize
from repro.kernels.sparse_conv.ops import tile_candidates

METHODS = ("dense", "lowered", "csr-direct", "pallas", "bsr")

# Value-storage dtypes the kernel candidates enumerate: f32 banks plus the
# quantised (per-output-channel symmetric scale, f32 accumulate) narrow
# formats.  Only the Pallas paths (pallas / bsr) execute narrow banks —
# dense / lowered / csr-direct candidates stay float32.  Callers (the
# planner) filter this by backend capability: fp8 requires a TPU backend.
VALUE_DTYPES = ("float32", "int8", "float8_e4m3fn")


def allowed_value_dtypes(backend: str) -> Tuple[str, ...]:
    """The value-storage dtypes executable on ``backend`` — the single
    capability policy the planner (candidate filtering) and the static
    verifier (pre-flight plan audits) share.  fp8 (``float8_e4m3fn``)
    needs TPU hardware casts; int8 and f32 run everywhere the Pallas
    paths do (including interpret mode)."""
    if backend == "tpu":
        return VALUE_DTYPES
    return tuple(d for d in VALUE_DTYPES if d != "float8_e4m3fn")

# ELL K-padding buckets (the paper's kernel-customization table keys on K
# granularity).  8 is the repo-wide default; 4 trims padded work on very
# sparse rows; 16 shares jit specialisations across near-equal layers.
PAD_TO_BUCKETS = (4, 8, 16)

# Cap on pallas tilings enumerated per (layer, pad_to, fuse): tile_candidates
# is preference-sorted, so the head of the list is the schedules worth
# measuring.
MAX_TILINGS = 24


@dataclasses.dataclass(frozen=True)
class ConvGeometry:
    """Static description of one conv layer instance (what the cache keys on).

    m/c: out/in channels; h/w: input spatial dims; r/s: filter dims.
    ``relu``/``residual`` describe the epilogue the engine fused into this
    conv at lowering time — they shape the candidate space (the ``fuse``
    axis) and the roofline's epilogue-traffic accounting, so fused and
    unfused variants of an otherwise identical geometry never share a plan.
    """

    name: str
    m: int
    c: int
    h: int
    w: int
    r: int
    s: int
    stride: int = 1
    pad: int = 0
    sparsity: float = 0.0
    batch: int = 1
    dtype: str = "float32"
    relu: bool = False
    residual: bool = False

    @property
    def hp(self) -> int:
        return self.h + 2 * self.pad

    @property
    def wp(self) -> int:
        return self.w + 2 * self.pad

    @property
    def e(self) -> int:
        return (self.hp - self.r) // self.stride + 1

    @property
    def f(self) -> int:
        return (self.wp - self.s) // self.stride + 1

    @property
    def row_nnz_est(self) -> int:
        """Expected nonzeros per output channel at this sparsity."""
        return max(1, math.ceil(self.c * self.r * self.s * (1.0 - self.sparsity)))

    def k_est(self, pad_to: int) -> int:
        """Estimated padded ELL row length K for a given pad_to bucket."""
        return ell_k(self.row_nnz_est, pad_to)

    def bsr_grid(self, bm: int, bn: int) -> Tuple[int, int, int]:
        """(gbm, gbn, kept-per-row estimate) of a (bm, bn)-blocked bank.

        The kept estimate assumes block-structured pruning at this layer's
        sparsity (``core.pruning.block_prune_conv``) — the deal the BCSR
        path offers.  On unstructured-pruned weights nearly every tile
        survives and the real bank is denser than this estimate; execution
        stays correct, only slower than priced.
        """
        gbm = -(-self.m // bm)
        gbn = -(-(self.c * self.r * self.s) // bn)
        kept = min(gbn, max(1, math.ceil((1.0 - self.sparsity) * gbn)))
        return gbm, gbn, kept


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the customization space.

    tm/te are only meaningful for the pallas method (te = None means the
    untiled full-extent spatial schedule); pad_to only for the sparse
    formats (lowered / csr-direct / pallas); ``fuse`` only for pallas and
    bsr — True executes the epilogue in-kernel; ``pipeline`` only for
    pallas — True double-buffers the halo DMA; ``permute`` only for pallas
    — True runs an nnz-balanced bank with the inverse permutation applied
    to the output; ``block_m``/``block_n`` only for bsr — the BCSR tile
    shape (te is meaningful for bsr too); ``value_dtype`` only for
    pallas and bsr — the bank's value-storage dtype ("float32", or the
    quantised "int8"/"float8_e4m3fn" with per-output-channel f32 scales
    and f32 accumulation).
    """

    method: str
    tm: Optional[int] = None
    pad_to: Optional[int] = None
    te: Optional[int] = None
    fuse: bool = False
    pipeline: bool = False
    permute: bool = False
    block_m: Optional[int] = None
    block_n: Optional[int] = None
    value_dtype: str = "float32"

    def to_dict(self) -> dict:
        return {"method": self.method, "tm": self.tm, "pad_to": self.pad_to,
                "te": self.te, "fuse": self.fuse,
                "pipeline": self.pipeline, "permute": self.permute,
                "block_m": self.block_m, "block_n": self.block_n,
                "value_dtype": self.value_dtype}

    @classmethod
    def from_dict(cls, d: dict) -> "Candidate":
        return cls(method=d["method"], tm=d.get("tm"), pad_to=d.get("pad_to"),
                   te=d.get("te"),
                   fuse=bool(d.get("fuse", False)),
                   pipeline=bool(d.get("pipeline", False)),
                   permute=bool(d.get("permute", False)),
                   block_m=d.get("block_m"), block_n=d.get("block_n"),
                   value_dtype=d.get("value_dtype", "float32"))


def pallas_feasible(g: ConvGeometry, k: int,
                    value_dtype: str = "float32") -> bool:
    """The Pallas kernel needs SMEM-resident packed indices (+ bias row, +
    the scale row for a quantised bank) and at least one VMEM-feasible
    (tm, te) tiling at the bank's value width.  Stride is handled
    in-kernel."""
    vsize = value_itemsize(value_dtype)
    if not smem_fits(g.m, k, vsize == 1):
        return False
    return bool(tile_candidates(g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride,
                                value_itemsize=vsize))


def bsr_feasible(g: ConvGeometry, bm: int, bn: int) -> bool:
    """The BCSR conv kernel needs its SMEM-resident block-column table and
    at least one VMEM-feasible row tiling for this block
    shape.

    The SMEM gate uses ``gbn`` — the largest KB any real bank of this
    geometry can pad to — not the mean kept estimate: the runtime check in
    ``ops.bsr_conv`` sees the bank's actual (max-row) KB, and a static
    gate below that bound could emit plans whose kernels silently fall
    back at execution time.
    """
    gbm, gbn, _ = g.bsr_grid(bm, bn)
    if not bsr_smem_fits(gbm, gbn):
        return False
    return bool(bsr_tile_candidates(g.c, g.e, g.f, g.r, g.s, g.stride,
                                    bm, bn))


def enumerate_candidates(g: ConvGeometry,
                         methods: Tuple[str, ...] = METHODS,
                         value_dtypes: Tuple[str, ...] = ("float32",),
                         row_nnz: Optional[int] = None,
                         ) -> List[Candidate]:
    """All statically-valid customization points for one layer.

    Every emitted pallas ``(tm, te)`` fits the VMEM budget (via
    ``kernels.sparse_conv.ops.tile_candidates`` — the heuristic the tuner
    refines; the list is preference-sorted and capped at MAX_TILINGS); every
    pallas candidate fits the SMEM budget.  Pallas points enumerate the
    full schedule cross product: unfused and fused (in-kernel epilogue)
    variants — fused-residual tilings reserve VMEM for the shortcut input
    tile — each in blocking and double-buffered (``pipeline``) halo DMA
    flavours — pipelined tilings reserve VMEM for the second halo block,
    so their feasible sets can be smaller — and each tiling additionally in
    an nnz-balanced (``permute``) variant.  BSR points enumerate the block
    shape ladder x feasible spatial tilings x the fuse axis.

    ``value_dtypes`` is the value-storage axis: both Pallas paths enumerate
    each requested dtype with its own feasibility probe (a quantised bank's
    smaller value block can make tilings feasible that f32 busts, and its
    scale row tightens the SMEM gate).  The default is float32 only —
    narrow storage is lossy, so quantised candidates enter the space only
    when a caller opts in (``plan_layer(..., quantize=True)`` passes the
    backend-filtered ``allowed_value_dtypes``; fp8 is dropped off-TPU to
    keep unexecutable points out of the measured space).  Dense / lowered /
    csr-direct candidates stay float32 always.

    ``row_nnz`` is the built bank's longest row when the weights are
    known: the ELL K it pads to decides the kernel's SMEM tiles, and the
    sparsity estimate undershoots it on a magnitude-pruned bank.
    """
    if g.sparsity <= 0.0:
        # Dense-kept layers (paper: conv1 et al.) have no sparse format.
        return [Candidate("dense")]
    out: List[Candidate] = []
    if "dense" in methods:
        out.append(Candidate("dense"))
    if "bsr" in methods:
        itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
        for vdt in value_dtypes:
            vsize = value_itemsize(vdt)
            quantized = vsize == 1
            for bm, bn in BLOCK_CANDIDATES:
                # SMEM gate at gbn, the worst-case KB any real bank pads to —
                # the runtime check sees the actual (max-row) KB, and a
                # mean-estimate gate could emit plans that silently fall back.
                gbm, gbn, _ = g.bsr_grid(bm, bn)
                if not bsr_smem_fits(gbm, gbn):
                    continue
                for fuse in (False, True):
                    tilings = bsr_tile_candidates(
                        g.c, g.e, g.f, g.r, g.s, g.stride, bm, bn,
                        itemsize=itemsize,
                        fuse_res=fuse and g.residual,
                        value_itemsize=vsize,
                        quantized=quantized)[:MAX_TILINGS]
                    for te in tilings:
                        out.append(Candidate("bsr", te=te, fuse=fuse,
                                             block_m=bm, block_n=bn,
                                             value_dtype=vdt))
    for pad_to in PAD_TO_BUCKETS:
        k = g.k_est(pad_to) if row_nnz is None else ell_k(row_nnz, pad_to)
        if "lowered" in methods:
            out.append(Candidate("lowered", pad_to=pad_to))
        if "csr-direct" in methods:
            out.append(Candidate("csr-direct", pad_to=pad_to))
        if "pallas" not in methods:
            continue
        for vdt in value_dtypes:
            vsize = value_itemsize(vdt)
            if not smem_fits(g.m, k, vsize == 1):
                continue
            for fuse in (False, True):
                # Pipelined first: the scorer keeps the earliest candidate
                # on ties, and on memory-bound layers the two schedules'
                # roofline totals tie while the pipelined one strictly cuts
                # the VPU staging stall — never worse, so it wins ties.
                for pipe in (True, False):
                    tilings = tile_candidates(
                        g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride,
                        fuse_res=fuse and g.residual,
                        pipeline=pipe, value_itemsize=vsize)[:MAX_TILINGS]
                    for tm, te in tilings:
                        for permute in (False, True):
                            out.append(Candidate(
                                "pallas", tm=tm, pad_to=pad_to, te=te,
                                fuse=fuse, pipeline=pipe, permute=permute,
                                value_dtype=vdt))
    return out
