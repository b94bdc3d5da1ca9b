"""Candidate scoring: wall-clock measurement with an analytical fallback.

Two scoring modes, both returning seconds (lower is better):

  ``mode="wall"``     -- jit + warmup + median-of-k wall time (the canonical
                         timer; ``benchmarks/common.py`` re-exports it).  The
                         Pallas kernels (ELL ``pallas`` and BCSR ``bsr``) are
                         only wall-timed on a real TPU backend — in interpret
                         mode their Python-executed time is meaningless, so
                         they are excluded from measurement.
  ``mode="roofline"`` -- analytic max(compute, memory) bound reusing the
                         constants of ``launch/roofline.py``.  Used in CI /
                         interpret mode and whenever measurement is disabled;
                         also how pallas-vs-rest is ranked on CPU.
"""
from __future__ import annotations

import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.direct_conv import dense_conv, direct_sparse_conv
from repro.core.lowering import lowered_sparse_conv
from repro.core.sparse_format import (balance_ell_conv, bcsr_conv_from_dense,
                                      ell_from_dense, ell_from_dense_conv,
                                      quantize_values)
from repro.kernels.bsr_conv.ops import bsr_conv
from repro.kernels.sparse_conv.ops import apply_epilogue, sparse_conv
from repro.kernels.window import halo_extent
from repro.launch.roofline import (HBM_BW, PEAK_FLOPS, VPU_FLOPS,
                                   value_itemsize)
from repro.tuning.space import Candidate, ConvGeometry


def _value_stream_bytes(n_values: float, m_rows: int, itemsize: int,
                        value_dtype: str) -> float:
    """HBM bytes of one sparse value stream: the values at their storage
    width plus, for a quantised dtype, the per-output-channel f32 scale
    row.  ``itemsize`` is the bank's native width (the input dtype's) —
    what a ``value_dtype="float32"`` candidate streams; quantised dtypes
    are priced at ``roofline.value_itemsize`` instead.  This is the
    roofline's byte credit for narrow value storage, and the reason every
    int8 bench row reports strictly fewer HBM bytes than its f32 twin
    (scale row < saved value bytes whenever a row has >= 2 values)."""
    if value_dtype == "float32":
        return float(n_values) * itemsize
    return float(n_values) * value_itemsize(value_dtype) + 4.0 * m_rows


class TimingStats(float):
    """Median wall seconds with the (min, max) spread riding along.

    A ``float`` subclass whose value *is* the p50, so every existing
    caller's arithmetic (``t * 1e3``, comparisons, sorting) keeps working
    unchanged, while ``.min`` / ``.max`` expose the measurement spread —
    a wide spread means the median was lucky, not representative.
    """

    __slots__ = ("min", "max")

    def __new__(cls, p50: float, tmin: Optional[float] = None,
                tmax: Optional[float] = None) -> "TimingStats":
        self = super().__new__(cls, p50)
        self.min = float(p50 if tmin is None else tmin)
        self.max = float(p50 if tmax is None else tmax)
        return self

    @property
    def p50(self) -> float:
        return float(self)

    @property
    def spread(self) -> float:
        return self.max - self.min

    def __repr__(self) -> str:
        return (f"TimingStats(p50={float(self):.3e}, min={self.min:.3e}, "
                f"max={self.max:.3e})")


def time_fn(fn: Callable, *args, warmup: int = 2,
            iters: int = 5) -> TimingStats:
    """(min, p50, max) wall time of a jitted call, as a :class:`TimingStats`
    (a float equal to the median, so callers doing arithmetic are
    unaffected; the spread makes noisy measurements visible)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times.sort()
    return TimingStats(times[len(times) // 2], times[0], times[-1])


# ---------------------------------------------------------------------------
# analytic roofline scoring
# ---------------------------------------------------------------------------

def epilogue_bytes(g: ConvGeometry, fused: bool) -> float:
    """HBM traffic the conv's epilogue (bias / ReLU / shortcut) costs.

    Unfused, every epilogue stage is a full round-trip of the output tensor:
    the bias add reads and rewrites it (plus the bias row), the ReLU reads
    and rewrites it again, and a bottleneck shortcut reads the output, the
    shortcut tensor, and writes once more.  Fused, the epilogue runs on the
    f32 accumulator in VMEM — only the bias row and (for bottleneck tails)
    one read of the shortcut tensor ever touch HBM.  This is the tuner's
    credit for the saved passes.
    """
    n, m = g.batch, g.m
    dout = float(n * m * g.e * g.f * 4)
    bias = float(m * 4)
    if fused:
        return bias + (dout if g.residual else 0.0)
    extra = 2 * dout + bias                       # bias pass
    if g.relu:
        extra += 2 * dout                         # ReLU pass
    if g.residual:
        extra += 2 * dout + dout                  # add pass + shortcut read
    return extra


def permute_bytes(g: ConvGeometry, permuted: bool) -> float:
    """HBM traffic the nnz-balanced bank's inverse output permutation costs:
    one read + one write of the f32 output tensor (the gather restoring
    natural channel order), plus the permutation row itself."""
    if not permuted:
        return 0.0
    return 2.0 * g.batch * g.m * g.e * g.f * 4 + g.m * 4


def staged_input_bytes(g: ConvGeometry, cand: Candidate) -> float:
    """Input bytes the Pallas kernel stages HBM->VMEM over the whole launch:
    one halo'd band of whole rows per (image, row-tile) grid cell.  Smaller
    te tiles re-fetch more halo overlap — the tuner's main spatial
    signal."""
    e, f = g.e, g.f
    itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
    te = min(cand.te or e, e)
    halo_h = halo_extent(te, g.stride, g.r)
    halo_w = halo_extent(f, g.stride, g.s)
    cells = (e + te - 1) // te
    return float(g.batch * cells * g.c * halo_h * halo_w * itemsize)


def _pallas_terms(g: ConvGeometry, cand: Candidate):
    """(compute_s, staged_s, other_mem_s) for one pallas candidate.

    Compute: the kernel's per-row loop is bounded by that row's true nnz
    and the TM-tile's rows execute sequentially on the TPU's single
    sequential grid, so tile compute is the *sum* of row nnz — invariant
    under row permutation — priced at the VPU FMA rate (the per-nonzero
    broadcast-FMA loop issues on the vector unit; the systolic arrays are
    the bsr path's territory, :func:`_bsr_terms`).  The analytic bound is
    therefore the true flop count for balanced and natural-order banks
    alike; ``permute`` only
    shows up on the memory side (the inverse-permutation gather,
    :func:`permute_bytes`).  Any scheduling benefit of near-equal rows per
    unrolled tile (the GPU-side balancing win of Yao et al.,
    arXiv:1811.00206) is below this model's resolution — wall-mode tuning
    is what can detect it.  Other memory: output + ELL + epilogue (+ the
    permute gather's output round-trip).
    """
    n, m = g.batch, g.m
    e, f = g.e, g.f
    itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
    k_pad = g.k_est(cand.pad_to or 8)
    nnz = float(m * g.row_nnz_est)
    fl = 2.0 * n * nnz * e * f
    dout = float(n * m * e * f * 4)
    ell_bytes = (_value_stream_bytes(m * k_pad, m, itemsize, cand.value_dtype)
                 + float(m * k_pad * 4))  # + packed index
    other = (dout + ell_bytes + epilogue_bytes(g, fused=cand.fuse)
             + permute_bytes(g, cand.permute))
    return (fl / VPU_FLOPS, staged_input_bytes(g, cand) / HBM_BW,
            other / HBM_BW)


def bcsr_true_kept(w_dense: np.ndarray, bm: int, bn: int) -> float:
    """Mean kept (any-nonzero) tiles per block-row of the *actual* bank a
    (bm, bn)-blocked ``bcsr_conv_from_dense`` would build from ``w_dense``.

    The geometry-only estimate (``ConvGeometry.bsr_grid``) assumes
    block-structured pruning; on unstructured magnitude-pruned weights
    nearly every tile contains a nonzero, so the real bank is far denser.
    When the planner has the weights in hand it recosts bsr candidates
    with this true count instead of the estimate.
    """
    w = np.asarray(w_dense)
    m = w.shape[0]
    flat = w.reshape(m, -1)
    n2 = flat.shape[1]
    pm, pn = (-m) % bm, (-n2) % bn
    wp = np.pad(flat, ((0, pm), (0, pn)))
    gbm, gbn = wp.shape[0] // bm, wp.shape[1] // bn
    tiles = wp.reshape(gbm, bm, gbn, bn).transpose(0, 2, 1, 3)
    keep = (tiles != 0).any(axis=(2, 3))
    return max(1.0, float(keep.sum(axis=1).mean()))


def _bsr_terms(g: ConvGeometry, cand: Candidate,
               kept_override: Optional[float] = None):
    """(compute_s, staged_s, other_mem_s) for one bsr (BCSR MXU) candidate.

    Compute has two serialized stages per kept weight tile: the *gather*
    (VPU — bn strided windows of te*f elements copied from the staged halo
    block into the patch tile) and the *contraction* (MXU — one
    (bm, bn) x (bn, te*f) systolic pass at the dense-unit peak).  Bigger
    bm amortises the gather over more systolic rows; that ratio is the
    tile-gather-vs-systolic-compute tradeoff this model prices against the
    ELL kernel's pure-VPU FMA loop (:func:`_pallas_terms`).  Kept-block
    counts assume block-structured pruning at the layer's sparsity
    (``ConvGeometry.bsr_grid``) unless ``kept_override`` supplies the
    actual bank's mean kept-per-row (:func:`bcsr_true_kept` — what the
    planner passes when it has the layer's weights).  Memory: the same
    halo staging model as the ELL kernel (blocking DMA), plus the kept
    weight tiles, the f32 output, and the epilogue traffic.
    """
    bm, bn = cand.block_m or 8, cand.block_n or 128
    gbm, _, kept = g.bsr_grid(bm, bn)
    if kept_override is not None:
        kept = kept_override
    n = g.batch
    e, f = g.e, g.f
    itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
    te = min(cand.te or e, e)
    cells = (e + te - 1) // te
    mxu_fl = 2.0 * n * gbm * kept * bm * bn * e * f
    gather_elems = float(n * cells * gbm * kept * bn * te * f)
    compute_s = mxu_fl / PEAK_FLOPS + gather_elems / VPU_FLOPS
    dout = float(n * gbm * bm * e * f * 4)
    w_bytes = _value_stream_bytes(gbm * kept * bm * bn, gbm * bm, itemsize,
                                  cand.value_dtype)
    other = dout + w_bytes + epilogue_bytes(g, fused=cand.fuse)
    return (compute_s, staged_input_bytes(g, cand) / HBM_BW, other / HBM_BW)


def staging_stall_s(g: ConvGeometry, cand: Candidate) -> float:
    """Seconds the VPU idles waiting on staged-input DMA under this schedule.

    Blocking (``pipeline=False``): every cell's halo copy is a
    ``start(); wait()`` pair — the VPU idles for the entire copy, so the
    full staged-input time is exposed.  Double-buffered
    (``pipeline=True``): each cell's copy flies behind the previous cell's
    FMA work, so the VPU only waits for the part of the copy that outlasts
    compute.  Strictly smaller than the blocking stall whenever there is
    any compute to hide behind (always, for a nonzero filter bank).  Note
    this is a VPU-wait metric, not a total-time delta: the copied bytes
    still cross the shared HBM bus, which :func:`roofline_estimate` keeps
    in the memory term for both schedules.
    """
    terms = (_bsr_terms if cand.method == "bsr" else _pallas_terms)(g, cand)
    t_fl, t_stage, _ = terms
    if not cand.pipeline:
        return t_stage
    return max(0.0, t_stage - t_fl)


def roofline_estimate(g: ConvGeometry, cand: Candidate,
                      w_dense: Optional[np.ndarray] = None,
                      bsr_kept: Optional[float] = None) -> float:
    """max(compute, memory) time bound for one candidate, in seconds.

    ``w_dense`` (optional) supplies the layer's actual pruned weights; it
    only affects bsr candidates, whose kept-block counts are then measured
    from the real bank (:func:`bcsr_true_kept`) instead of assuming
    block-structured pruning at the nominal sparsity.  ``bsr_kept``
    short-circuits that scan with a precomputed mean kept-per-row (the
    planner computes it once per block shape, not once per candidate).

    Mirrors the per-method byte/flop accounting of fig8's TPU projection,
    refined with the execution-unit split: dense conv and the bsr path
    contract on the MXU (``PEAK_FLOPS``), while the per-nonzero FMA loops
    of lowered / csr-direct / pallas issue on the VPU (``VPU_FLOPS``) —
    the crossover that makes block sparsity worthwhile at moderate
    densities, and the reason moderately-sparse large-channel layers used
    to be stuck below the dense roofline:

      dense       streams input + output + dense weights; full dense flops
                  at the MXU peak.
      lowered     materialises the duplicated im2col matrix twice (write +
                  read) — the bandwidth waste the paper's direct method
                  removes; sparse VPU flops over the padded ELL rows.
      csr-direct  streams input + output + ELL (value, packed idx); the scan
                  covers all K padded slots, so padded K costs (VPU) flops.
      bsr         the BCSR MXU path: same halo staging model as pallas
                  (blocking DMA), kept weight tiles streamed, compute =
                  serialized VPU patch gather + MXU tile contractions
                  (:func:`_bsr_terms` — the gather-vs-systolic tradeoff).
      pallas      same traffic, but the halo'd input block is staged
                  HBM->VMEM once per (image, row-tile) grid cell and
                  reused across channel tiles: smaller te tiles cost
                  more halo re-fetch (the tuner's main spatial signal),
                  while the nnz loop bound skips padding, so padded K costs
                  no flops (see :func:`_pallas_terms` for why the bound is
                  permutation-invariant; an nnz-balanced ``permute`` bank
                  additionally pays the inverse-permutation gather,
                  :func:`permute_bytes`).  The halo DMA schedule decides
                  how staging composes: blocking stages with
                  ``start(); wait()``, so the VPU idles for every copy and
                  the bound is ``staged + max(compute, other-traffic)``;
                  double-buffered (``pipeline``) staging overlaps the
                  copies with compute, recovering the classic
                  ``max(compute, staged + other-traffic)`` — staging and
                  other traffic still *sum* in the memory term (they share
                  the HBM bus; overlap hides latency, it does not
                  manufacture bandwidth).  The recovered VPU idle time is
                  the pipeline's roofline credit (:func:`staging_stall_s`
                  exposes each schedule's stall for the bench tables).

    Every method additionally pays its epilogue traffic
    (:func:`epilogue_bytes`): the unfused bias/ReLU/shortcut passes for
    dense/lowered/csr-direct and unfused pallas, or just the bias row (+ one
    shortcut read) for a fused pallas candidate — the saved output passes
    are the fused epilogue's roofline credit.
    """
    n, m, c = g.batch, g.m, g.c
    rs = g.r * g.s
    e, f = g.e, g.f
    itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
    din = float(n * c * g.hp * g.wp * itemsize)
    dout = float(n * m * e * f * 4)          # f32 accumulate
    dense_fl = 2.0 * n * m * c * rs * e * f
    ep_unfused = epilogue_bytes(g, fused=False)
    if cand.method == "dense":
        return max(dense_fl / PEAK_FLOPS,
                   (din + dout + itemsize * m * c * rs + ep_unfused) / HBM_BW)
    if cand.method == "bsr":
        # Blocking halo DMA (like un-pipelined pallas): the unit stalls for
        # every cell's staged copy, so staging serialises with the rest.
        # With the layer's weights in hand, kept-block counts come from the
        # *actual* bank — unstructured magnitude-pruned weights keep nearly
        # every tile, and pricing them with the block-structured estimate
        # would route such layers to a slower-than-dense schedule.
        kept = bsr_kept
        if kept is None and w_dense is not None:
            kept = bcsr_true_kept(w_dense, cand.block_m or 8,
                                  cand.block_n or 128)
        t_c, t_stage, t_other = _bsr_terms(g, cand, kept_override=kept)
        return t_stage + max(t_c, t_other)
    k_pad = g.k_est(cand.pad_to or 8)
    ell_bytes = float(m * k_pad * (itemsize + 4))  # value + packed index
    padded_fl = 2.0 * n * m * k_pad * e * f
    if cand.method == "lowered":
        im2col = float(n * c * rs * e * f * itemsize)
        return max(padded_fl / VPU_FLOPS,
                   (2 * im2col + dout + ell_bytes + ep_unfused) / HBM_BW)
    if cand.method == "csr-direct":
        return max(padded_fl / VPU_FLOPS,
                   (din + dout + ell_bytes + ep_unfused) / HBM_BW)
    if cand.method == "pallas":
        t_fl, t_stage, t_other = _pallas_terms(g, cand)
        if cand.pipeline:
            # Copies overlap compute; all bytes still share HBM bandwidth.
            return max(t_fl, t_stage + t_other)
        # Blocking start();wait(): the VPU idles for every cell's copy, so
        # staging serialises with the max of compute and other traffic.
        return t_stage + max(t_fl, t_other)
    raise ValueError(cand.method)


def candidate_cost(g: ConvGeometry, cand: Candidate,
                   w_dense: Optional[np.ndarray] = None,
                   bsr_kept: Optional[float] = None) -> dict:
    """Roofline attribution for one candidate: the flop count, total HBM
    bytes, staging-stall seconds, and the :func:`roofline_estimate` bound,
    as one dict — what the engine's ExecutionReport charges each op.

    The flop/byte terms are exactly the ones :func:`roofline_estimate`
    prices (per-method execution-unit split and all); this just returns
    them instead of collapsing to the max.  ``staging_stall_s`` is nonzero
    only for the halo-staging kernels (pallas / bsr).
    """
    n, m, c = g.batch, g.m, g.c
    rs = g.r * g.s
    e, f = g.e, g.f
    itemsize = 2 if g.dtype in ("bfloat16", "float16") else 4
    din = float(n * c * g.hp * g.wp * itemsize)
    dout = float(n * m * e * f * 4)
    ep_unfused = epilogue_bytes(g, fused=False)
    est_s = roofline_estimate(g, cand, w_dense=w_dense, bsr_kept=bsr_kept)
    stall = (staging_stall_s(g, cand)
             if cand.method in ("pallas", "bsr") else 0.0)
    if cand.method == "dense":
        flops = 2.0 * n * m * c * rs * e * f
        hbm = din + dout + itemsize * m * c * rs + ep_unfused
    elif cand.method == "bsr":
        bm, bn = cand.block_m or 8, cand.block_n or 128
        gbm, _, kept = g.bsr_grid(bm, bn)
        if bsr_kept is not None:
            kept = bsr_kept
        elif w_dense is not None:
            kept = bcsr_true_kept(w_dense, bm, bn)
        flops = 2.0 * n * gbm * kept * bm * bn * e * f
        hbm = (staged_input_bytes(g, cand) + dout
               + _value_stream_bytes(gbm * kept * bm * bn, gbm * bm,
                                     itemsize, cand.value_dtype)
               + epilogue_bytes(g, fused=cand.fuse))
    elif cand.method == "pallas":
        flops = 2.0 * n * m * g.row_nnz_est * e * f
        k_pad = g.k_est(cand.pad_to or 8)
        hbm = (staged_input_bytes(g, cand) + dout
               + _value_stream_bytes(m * k_pad, m, itemsize, cand.value_dtype)
               + float(m * k_pad * 4)
               + epilogue_bytes(g, fused=cand.fuse)
               + permute_bytes(g, cand.permute))
    elif cand.method in ("lowered", "csr-direct"):
        k_pad = g.k_est(cand.pad_to or 8)
        flops = 2.0 * n * m * k_pad * e * f
        ell_bytes = float(m * k_pad * (itemsize + 4))
        if cand.method == "lowered":
            im2col = float(n * c * rs * e * f * itemsize)
            hbm = 2 * im2col + dout + ell_bytes + ep_unfused
        else:
            hbm = din + dout + ell_bytes + ep_unfused
    else:
        raise ValueError(cand.method)
    return {"flops": float(flops), "hbm_bytes": float(hbm),
            "staging_stall_s": float(stall), "est_s": float(est_s)}


# ---------------------------------------------------------------------------
# wall-clock scoring
# ---------------------------------------------------------------------------

def build_runner(g: ConvGeometry, cand: Candidate, w_dense: np.ndarray,
                 *, interpret: bool = True):
    """(fn, args) executing one candidate on a pruned dense (M, C, R, S) bank.

    Every runner executes the conv *plus its epilogue* (bias, and the
    ReLU/shortcut stages the geometry's fused-epilogue flags name), so
    fused and unfused candidates are wall-timed over the same math: unfused
    runners apply the epilogue as separate ops, a ``fuse=True`` pallas
    runner hands it to the kernel.
    """
    rng = np.random.default_rng(1)
    bias = jnp.zeros((g.m,), jnp.float32)
    res = (jnp.asarray(rng.standard_normal(
        (g.batch, g.m, g.e, g.f)).astype(np.float32))
        if g.residual else None)

    def epilogue(y):
        return apply_epilogue(y, bias, g.relu, res)

    if cand.method == "dense":
        fn = jax.jit(lambda x, w: epilogue(
            dense_conv(x, w, stride=g.stride, padding=g.pad)))
        return fn, (jnp.asarray(w_dense),)
    pad_to = cand.pad_to or 8
    if cand.method == "lowered":
        ell2d = ell_from_dense(w_dense.reshape(g.m, -1), pad_to=pad_to)
        fn = jax.jit(lambda x, e2d=ell2d: epilogue(lowered_sparse_conv(
            x, e2d, r=g.r, s=g.s, stride=g.stride, padding=g.pad)))
        return fn, ()
    if cand.method == "bsr":
        # The BCSR bank is built from the pruned weights *as given* — on
        # unstructured-pruned banks most tiles survive, and that denser
        # reality is exactly what the wall clock should see.
        bcc = bcsr_conv_from_dense(
            w_dense, block=(cand.block_m or 8, cand.block_n or 128))
        if cand.value_dtype != "float32":
            bcc = quantize_values(bcc, cand.value_dtype)
        if cand.fuse:
            return jax.jit(lambda x, b=bcc: bsr_conv(
                x, b, stride=g.stride, padding=g.pad, te=cand.te,
                bias=bias, fuse_relu=g.relu, residual=res,
                interpret=interpret)), ()
        return jax.jit(lambda x, b=bcc: epilogue(bsr_conv(
            x, b, stride=g.stride, padding=g.pad, te=cand.te,
            interpret=interpret))), ()
    ell = ell_from_dense_conv(w_dense, pad_to=pad_to)
    if cand.method == "csr-direct":
        fn = jax.jit(lambda x, e=ell: epilogue(direct_sparse_conv(
            x, e, stride=g.stride, padding=g.pad)))
        return fn, ()
    if cand.method == "pallas":
        # Both variants are wrapped in one outer jit so the unfused
        # epilogue's extra ops compile into the same dispatch as the conv —
        # anything else would bill eager-dispatch overhead to the unfused
        # schedule and bias the fused-vs-unfused comparison.  A permute
        # candidate runs the nnz-balanced bank (the inverse-permutation
        # gather it pays for is inside sparse_conv, so it is timed); the
        # pipeline flag picks the halo DMA schedule.  A quantised candidate
        # runs the int8/fp8 bank the plan would pin (scale row prefetched,
        # in-kernel dequantise — the cast cost is timed).
        if cand.value_dtype != "float32":
            ell = quantize_values(ell, cand.value_dtype)
        if cand.permute:
            ell = balance_ell_conv(ell)
        if cand.fuse:
            return jax.jit(lambda x, e=ell: sparse_conv(
                x, e, stride=g.stride, padding=g.pad, tm=cand.tm,
                te=cand.te, bias=bias, fuse_relu=g.relu,
                residual=res, pipeline=cand.pipeline,
                interpret=interpret)), ()
        return jax.jit(lambda x, e=ell: epilogue(sparse_conv(
            x, e, stride=g.stride, padding=g.pad, tm=cand.tm,
            te=cand.te, pipeline=cand.pipeline,
            interpret=interpret))), ()
    raise ValueError(cand.method)


def measure_candidate(g: ConvGeometry, cand: Candidate, w_dense: np.ndarray,
                      x: jax.Array, *, warmup: int = 1, iters: int = 5,
                      interpret: bool = True) -> TimingStats:
    """Median wall seconds (+ spread) for one candidate on real arrays."""
    runner, extra = build_runner(g, cand, w_dense, interpret=interpret)
    if extra:  # dense path: (x, w)
        return time_fn(runner, x, *extra, warmup=warmup, iters=iters)
    return time_fn(runner, x, warmup=warmup, iters=iters)


def measurable(cand: Candidate, backend: str) -> bool:
    """Whether wall-timing this candidate is meaningful on ``backend``.

    Pallas kernels (the ELL ``pallas`` path and the BCSR ``bsr`` path) in
    interpret mode are Python-executed — their wall time says nothing about
    the kernel, so off-TPU they are scored by roofline only.
    """
    return cand.method not in ("pallas", "bsr") or backend == "tpu"
