"""Jit'd public wrapper around the direct sparse conv Pallas kernel.

Handles: input padding (pad_in), index packing, tile selection — output
channels ``tm`` and output row tiles ``te``, the paper's
kernel-customisation table — dtype policy (bf16/f32 in, f32 accumulate),
the fused epilogue (bias / ReLU / bottleneck residual applied to the f32
accumulator in-kernel, one output write instead of three HBM passes), the
halo DMA schedule (``pipeline=True`` double-buffers the staged input block
so the copy for spatial cell i+1 overlaps cell i's compute; auto-enabled
whenever the second halo buffer fits VMEM), nnz-balanced banks (an
``EllConv`` carrying a row permutation runs the kernel in balanced row
order — bias/residual are permuted in, the output is inverse-permuted
back, so callers never see the reordering), and the fallback to the
pure-JAX direct path for layers whose packed index array busts the SMEM
budget or for which no VMEM-feasible tiling exists — the fallback applies
the same epilogue unfused, so ``sparse_conv`` is a complete conv+epilogue
operator either way.

Strided layers and tall feature maps run through the Pallas kernel: the
kernel tiles the output rows with halo'd input bands, and the wrapper's
column phase split lets it read every strided window as a static lane
slice.  Only tilings the TPU can block are emitted (``budget.ell_tiling_ok``:
channel tiles a multiple of 8 or all of M, row tiles a multiple of 8 or all
of E, whole output rows).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.direct_conv import direct_sparse_conv, out_spatial
from repro.core.sparse_format import (EllConv, dequantize,
                                      ell_from_dense_conv,
                                      inverse_permutation)
from repro.kernels import budget
from repro.kernels.budget import (channel_tile_ok, value_itemsize,
                                  vmem_tile_bytes)
from repro.kernels.sparse_conv.kernel import sparse_conv_pallas
from repro.kernels.window import halo_extent
from repro.telemetry.fallback import record_fallback

# Budget constants live in ``repro.kernels.budget`` (one source of truth for
# kernels, tuner, and the static verifier); these module aliases stay so
# existing callers — and tests that monkeypatch them — keep working.  The
# fit wrappers below re-read the aliases at call time and pass them through.
_VMEM_BUDGET = budget.VMEM_BUDGET
_SMEM_BUDGET = budget.SMEM_BUDGET

# Public aliases consumed by repro.tuning (candidate-space pruning).
VMEM_BUDGET = _VMEM_BUDGET
SMEM_BUDGET = _SMEM_BUDGET

_TM_LADDER = (128, 64, 32, 16, 8)
# Output row tile ladder (besides the untiled full extent).
_SPATIAL_LADDER = (128, 64, 32, 16, 8)


def tm_ladder(m: int) -> List[int]:
    """Legal channel tiles for M, largest first: the ladder's multiples of
    8 that divide M, plus M itself when it is small or the only legal
    tile."""
    tms = {t for t in _TM_LADDER if channel_tile_ok(m, t)}
    if m <= _TM_LADDER[0] or m % 8:
        tms.add(m)
    return sorted(tms, reverse=True)


def smem_fits(m: int, k: int, quantized: bool = False,
              tm: Optional[int] = None) -> bool:
    """The kernel's SMEM operands fit at channel tile ``tm`` (default: the
    smallest legal tile): the double-buffered (TM, K) packed-index and
    value tiles, the int32 nnz row (M*4 — the kernel's per-row loop bounds;
    omitting it used to let index-heavy layers overshoot), the f32 bias row
    (M*4), and — for a quantised bank — the f32 per-channel scale row
    (another M*4)."""
    return budget.smem_fits(m, k, quantized, tm=tm, smem_budget=_SMEM_BUDGET)


def spatial_candidates(e: int) -> List[int]:
    """Output row tiles to consider, largest first.

    The full extent (untiled) comes first — when it fits it is the best
    schedule (no halo re-fetch); the ladder below it (multiples of 8, the
    TPU's sublane tile) trades halo overlap for a bounded VMEM block on
    tall feature maps.  Columns are never tiled: the kernel stages whole
    lane rows.
    """
    return [e] + [t for t in _SPATIAL_LADDER if t < e]


def tm_candidates(m: int, c: int, hp: int, wp: int, e: int, f: int,
                  k: int, value_itemsize: int = 4) -> List[int]:
    """Legal output-channel tiles that fit VMEM and SMEM with the *whole*
    padded image staged (the untiled spatial schedule), largest first.

    Returns ``[]`` when even the smallest tile busts the budget — callers
    must then tile spatially (``tile_candidates``) or fall back to the
    pure-JAX path.  Returning ``[1]`` here used to launch an over-budget
    kernel.  ``value_itemsize`` prices the SMEM value tile at its storage
    width (1 for int8/fp8 quantised banks).
    """
    x_bytes = c * vmem_tile_bytes(hp, wp, 4)
    out: List[int] = []
    for tm in tm_ladder(m):
        out_bytes = 2 * tm * vmem_tile_bytes(e, f, 4)
        if (x_bytes + out_bytes <= _VMEM_BUDGET
                and budget.smem_fits(m, k, value_itemsize == 1, tm=tm,
                                     value_itemsize=value_itemsize,
                                     smem_budget=_SMEM_BUDGET)):
            out.append(tm)
    return out


def tiling_fits(m: int, c: int, e: int, f: int, k: int, r: int, s: int,
                stride: int, tm: int, te: int,
                fuse_res: bool = False, pipeline: bool = False,
                value_itemsize: int = 4) -> bool:
    """Whether one (tm, te) tiling is blockable on the TPU and its
    working set fits: in VMEM the halo'd input block + the f32 out tile
    (+ the residual input tile when the fused epilogue accumulates a
    shortcut), in SMEM the (TM, K) index and value tiles.

    ``pipeline=True`` accounts the double-buffered halo DMA schedule: two
    halo-block scratch buffers are live at once (the one being computed on
    and the one being prefetched), so the staged-input term doubles.
    ``value_itemsize`` prices the value tile at its storage width."""
    return budget.tiling_fits(m, c, e, f, k, r, s, stride, tm, te,
                              fuse_res=fuse_res, pipeline=pipeline,
                              value_itemsize=value_itemsize,
                              vmem_budget=_VMEM_BUDGET,
                              smem_budget=_SMEM_BUDGET)


def tile_candidates(m: int, c: int, e: int, f: int, k: int, r: int, s: int,
                    stride: int = 1,
                    tms: Optional[Tuple[int, ...]] = None,
                    fuse_res: bool = False, pipeline: bool = False,
                    value_itemsize: int = 4,
                    ) -> List[Tuple[int, int]]:
    """All blockable (tm, te) tilings whose working set fits, preferred
    first (a tile always spans all F columns).

    Preference order: fewest spatial cells (least halo re-fetch), then least
    total staged input traffic, then largest tm — so when the whole image
    fits, the first candidate is the untiled schedule with the largest
    feasible channel tile.  ``tms`` overrides the channel-tile ladder (e.g.
    a caller-pinned tm that the ladder doesn't contain); ``fuse_res``
    reserves VMEM for the fused epilogue's residual input tile; ``pipeline``
    for the double-buffered halo schedule's second scratch block;
    ``value_itemsize`` prices the value block at its storage width.
    """
    out: List[Tuple[int, int]] = []
    for te in spatial_candidates(e):
        for tm in (tms or tm_ladder(m)):
            if tiling_fits(m, c, e, f, k, r, s, stride, tm, te,
                           fuse_res=fuse_res, pipeline=pipeline,
                           value_itemsize=value_itemsize):
                out.append((tm, te))

    def pref(cand: Tuple[int, int]) -> Tuple[int, int, int]:
        tm, te = cand
        cells = -(-e // te)
        staged = cells * halo_extent(te, stride, r)
        return (cells, staged, -tm)

    return sorted(out, key=pref)


def choose_tiles(m: int, c: int, e: int, f: int, k: int, r: int, s: int,
                 stride: int = 1) -> Optional[Tuple[int, int]]:
    """Static heuristic seed: the preferred feasible (tm, te), or None
    when no tiling fits (caller falls back to the pure-JAX direct path)."""
    cands = tile_candidates(m, c, e, f, k, r, s, stride)
    return cands[0] if cands else None


def choose_tm(m: int, c: int, hp: int, wp: int, e: int, f: int, k: int) -> int:
    """Pick the largest output-channel tile whose untiled-spatial VMEM
    working set fits.

    Mirrors the paper's per-layer kernel specialisation: small, few-channel
    layers get a big TM (amortise the input stage-in); the measurement-driven
    refinement lives in ``repro.tuning``.  Raises when nothing fits — use
    ``choose_tiles`` (spatial tiling) for such layers.
    """
    cands = tm_candidates(m, c, hp, wp, e, f, k)
    if not cands:
        raise ValueError(
            f"no feasible untiled tm for m={m} c={c} hp={hp} wp={wp}; "
            "the feature map needs spatial tiling (choose_tiles)")
    return cands[0]


def pack_indices(ell: EllConv) -> jax.Array:
    """Pack (c, r, s) into one int32 per nonzero: c*(R*S) + r*S + s."""
    _, _, r, s = ell.shape
    return (ell.cidx * (r * s) + ell.ridx * s + ell.sidx).astype(jnp.int32)


def apply_epilogue(y: jax.Array, bias: Optional[jax.Array],
                   fuse_relu: bool,
                   residual: Optional[jax.Array]) -> jax.Array:
    """The unfused conv epilogue: same math as the kernel's fused one,
    applied as separate ops on the f32 result, then cast back to the input
    dtype.  The single definition the fallback path, the wall-clock
    runners, and the benchmark epilogue rows all share."""
    dtype = y.dtype
    y = y.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)[None, :, None, None]
    if residual is not None:
        y = y + residual.astype(jnp.float32)
    if fuse_relu:
        y = jax.nn.relu(y)
    return y.astype(dtype)


def resolve_schedule(m: int, c: int, e: int, f: int, k: int, r: int, s: int,
                     stride: int, *, tm: Optional[int] = None,
                     te: Optional[int] = None,
                     fuse_res: bool = False,
                     pipeline: Optional[bool] = None,
                     value_dtype: str = "float32",
                     ) -> Tuple[Optional[Tuple[int, int, bool]],
                                Optional[str]]:
    """The dispatch decision ``sparse_conv`` makes, as a pure function.

    Returns ``((tm, te, pipeline), None)`` for the schedule the Pallas
    kernel would run, or ``(None, reason)`` — a ``telemetry.fallback``
    reason code — when the layer falls back to the pure-JAX direct path.
    Factored out so the engine's ExecutionReport and the benchmark's
    zero-fallback invariant can ask "what would this layer execute?"
    without launching anything; ``sparse_conv`` itself dispatches through
    this same function.

    ``value_dtype`` names the bank's storage dtype: a quantised bank
    (int8 / float8_e4m3fn) shrinks the VMEM value block to one byte per
    nonzero but scalar-prefetches an extra f32 scale row in SMEM — both
    accounted here so feasibility matches what the kernel would allocate.
    """
    vsize = value_itemsize(value_dtype)
    quantized = vsize == 1
    if not budget.smem_fits(m, k, quantized, value_itemsize=vsize,
                            smem_budget=_SMEM_BUDGET):
        # Index-heavy layers: even the smallest channel tile's index and
        # value tiles overflow SMEM.
        return None, "smem_infeasible"
    if tm is not None and te is not None:
        # Fully-specified tiling (tuned plan / caller override): honor it
        # when it is blockable and fits, never launch an over-budget or
        # uncompilable kernel.
        te = min(te, e)
        if tm < 1 or m % tm:
            return None, "nondividing_tm"
        if not tiling_fits(m, c, e, f, k, r, s, stride, tm, te,
                           fuse_res=fuse_res, value_itemsize=vsize):
            return None, "no_feasible_tiling"
    else:
        # A pinned tm need not sit on the default ladder (e.g. tm=24 for
        # m=48): enumerate spatial tiles for exactly that tm.
        if tm is not None and (tm < 1 or m % tm):
            return None, "nondividing_tm"
        cands = tile_candidates(m, c, e, f, k, r, s, stride,
                                tms=None if tm is None else (tm,),
                                fuse_res=fuse_res, value_itemsize=vsize)
        if te is not None:
            cands = [t for t in cands if t[1] == min(te, e)]
        if not cands:
            # No in-budget tiling (or the requested one is infeasible).
            return None, "no_feasible_tiling"
        tm, te = cands[0]
    # Halo DMA schedule: double-buffer when allowed *and* the second halo
    # scratch block fits; otherwise the single-buffer blocking path.
    if pipeline is None or pipeline:
        pipeline = tiling_fits(m, c, e, f, k, r, s, stride, tm, te,
                               fuse_res=fuse_res, pipeline=True,
                               value_itemsize=vsize)
    return (tm, te, bool(pipeline)), None


def sparse_conv(x: jax.Array, ell: EllConv, *, stride: int = 1,
                padding: int = 0, tm: Optional[int] = None,
                te: Optional[int] = None,
                bias: Optional[jax.Array] = None, fuse_relu: bool = False,
                residual: Optional[jax.Array] = None,
                pipeline: Optional[bool] = None,
                interpret: bool = False,
                layer: Optional[str] = None) -> jax.Array:
    """Direct sparse convolution + fused epilogue, Pallas-accelerated.

    (N, C, H, W) input, ELL filter bank for (M, C, R, S) weights ->
    (N, M, E, F) in x.dtype.  Any stride >= 1 runs in-kernel; tm/te
    default to the static heuristic (``choose_tiles``) and are the knobs
    the ``repro.tuning`` autotuner turns.  ``bias`` (per-channel),
    ``fuse_relu`` and ``residual`` (a shortcut tensor shaped like the
    output) execute in-kernel on the f32 accumulator so the output is
    written to HBM exactly once.

    ``pipeline`` selects the halo DMA schedule: ``True`` double-buffers the
    staged input block (the copy for spatial cell i+1 overlaps cell i's
    compute), ``False`` forces the single-buffer blocking schedule, and
    ``None`` (default) auto-enables double buffering whenever the second
    halo block also fits VMEM.  A requested ``pipeline=True`` that busts
    the budget silently drops to the single-buffer path — same math,
    blocking staging — never to the pure-JAX fallback.

    An nnz-balanced bank (``ell.perm`` set, see
    ``core.sparse_format.balance_ell_conv``) runs the kernel in balanced
    row order: bias/residual are gathered into bank order on the way in and
    the output is inverse-permuted on the way out, so results are
    bit-identical to the natural-order bank (per-row accumulation order is
    untouched).  Falls back to the pure-JAX direct path — with the
    identical epilogue applied unfused — only when the packed index array
    busts the SMEM budget or no VMEM-feasible tiling exists; any such
    fallback is reported through ``telemetry.record_fallback`` (one-time
    warning + gated counters), ``layer`` naming the conv op when the
    caller knows it.
    """
    m, c, r, s = ell.shape
    k = ell.k
    inv = inverse_permutation(ell.perm) if ell.perm is not None else None
    n, _, h, w = x.shape
    e, f = out_spatial(h, w, r, s, stride, padding)
    fuse_res = residual is not None

    def fallback(reason: str) -> jax.Array:
        record_fallback(
            "sparse_conv", reason, layer=layer,
            geometry=(f"m={m} c={c} e={e} f={f} k={k} r={r} s={s} "
                      f"stride={stride}"),
            fallback_to="csr-direct")
        # The pure-JAX direct path multiplies values in their storage dtype;
        # a quantised bank must be dequantised first so the fallback computes
        # the same f32 math as the kernel's in-register scale.
        y = direct_sparse_conv(x, dequantize(ell), stride=stride,
                               padding=padding)
        if inv is not None:
            # The bank's rows are in balanced order; restore channel order
            # before the (caller-ordered) epilogue.
            y = jnp.take(y, inv, axis=1)
        return apply_epilogue(y, bias, fuse_relu, residual)

    sched, reason = resolve_schedule(m, c, e, f, k, r, s, stride, tm=tm,
                                     te=te, fuse_res=fuse_res,
                                     pipeline=pipeline,
                                     value_dtype=ell.value_dtype)
    if sched is None:
        # The XLA-scheduled direct path, with the same epilogue unfused.
        return fallback(reason)
    tm, te, pipeline = sched
    xpad = jnp.pad(x, ((0, 0), (0, 0), (padding, padding), (padding, padding)))
    b = (jnp.zeros((m,), jnp.float32) if bias is None
         else jnp.asarray(bias, jnp.float32))
    res = residual
    if ell.perm is not None:
        # Balanced bank: the kernel computes bank-row-ordered output, so its
        # per-row epilogue operands must be gathered into bank order too.
        b = jnp.take(b, ell.perm, axis=0)
        if res is not None:
            res = jnp.take(res, ell.perm, axis=1)
    out = sparse_conv_pallas(
        xpad, ell.value, pack_indices(ell), ell.nnz, b, res,
        scale=ell.scale,
        tm=tm, k=k, rs=r * s, s=s, e=e, f=f, stride=stride, te=te,
        fuse_relu=fuse_relu, pipeline=pipeline, interpret=interpret)
    if inv is not None:
        out = jnp.take(out, inv, axis=1)
    return out.astype(x.dtype)


def sparse_conv_from_dense(x: jax.Array, w_dense, *, stride: int = 1,
                           padding: int = 0, interpret: bool = False) -> jax.Array:
    """Convenience: prune-format-and-run from a dense (M, C, R, S) weight."""
    ell = ell_from_dense_conv(np.asarray(w_dense))
    return sparse_conv(x, ell, stride=stride, padding=padding, interpret=interpret)
