"""Network-level planner: plan a lowered engine program, per-conv-op.

The planner turns the static candidate space (``space.py``) plus a scoring
mode (``measure.py``) into a ``{layer_name: PlanEntry}`` plan, consulting and
filling a persistent :class:`~repro.tuning.cache.PlanCache` so tuning runs
once per deployment.  It operates on the engine's flat lowered program
(``repro.engine.lower``) — the spec is walked exactly once, by the engine,
and the planner iterates the resulting ``ConvOp`` list with every geometry
(including the fused-epilogue flags) already resolved.

Identical geometries (e.g. repeated ResNet bottlenecks) share one key and
are scored once per run even without a persistent cache; the key includes
the epilogue signature, so a bottleneck-tail conv (fused shortcut) never
reuses the measurement of a plain conv+ReLU with the same shape.
``models/cnn.py`` / ``CnnEngine`` execute the plan via ``method="auto"``.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Any, Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.sparse_format import (bcsr_conv_from_dense, ell_from_dense,
                                      ell_from_dense_conv, ell_k,
                                      quantize_values)
from repro.engine import ConvOp, Program, lower
from repro.kernels.sparse_conv.ops import resolve_schedule
from repro.tuning.cache import PlanCache, PlanEntry, layer_key
from repro.tuning.measure import (bcsr_true_kept, measurable,
                                  measure_candidate, roofline_estimate)
from repro.tuning.space import (ConvGeometry, allowed_value_dtypes,
                                enumerate_candidates)

_LOG = logging.getLogger("repro.tuning")


def geometry_for(layer: "spec.Conv", c: int, h: int, w: int, *, batch: int = 1,
                 dtype: str = "float32", relu: bool = False,
                 residual: bool = False) -> ConvGeometry:
    """Geometry from a raw layer spec (no epilogue flags unless given)."""
    return ConvGeometry(
        name=layer.name, m=layer.out_c, c=c, h=h, w=w, r=layer.k, s=layer.k,
        stride=layer.stride, pad=layer.pad, sparsity=layer.sparsity,
        batch=batch, dtype=dtype, relu=relu, residual=residual)


def geometry_of_op(op: ConvOp, *, batch: int = 1,
                   dtype: str = "float32") -> ConvGeometry:
    """Geometry from a lowered ``ConvOp`` — carries the fused-epilogue
    signature (ReLU / bottleneck shortcut) into the cache key and the
    candidate space's ``fuse`` axis."""
    return ConvGeometry(
        name=op.name, m=op.m, c=op.c, h=op.h, w=op.w, r=op.k, s=op.k,
        stride=op.stride, pad=op.pad, sparsity=op.sparsity, batch=batch,
        dtype=dtype, relu=op.fuse_relu, residual=op.res is not None)


def plan_layer(g: ConvGeometry, *, backend: str, mode: str = "roofline",
               w_dense: Optional[np.ndarray] = None,
               row_nnz: Optional[int] = None,
               warmup: int = 1, iters: int = 3,
               quantize: bool = False) -> PlanEntry:
    """Score every valid candidate for one layer and return the winner.

    ``backend`` is the platform the plan will run on (``"tpu"``,
    ``"cpu"``, ...).  Wall mode runs the Pallas kernels compiled on a TPU
    ``backend`` and
    times only the non-Pallas candidates elsewhere (``measurable``) —
    wall-timing an interpret-mode kernel would measure the Python
    interpreter, not the kernel.  ``w_dense`` is required for
    wall mode and *used* by roofline mode when given: bsr candidates are
    then priced from the actual bank's kept-block structure instead of
    the block-structured-pruning estimate (unstructured magnitude-pruned
    weights keep nearly every tile — the estimate would send such layers
    to a slower-than-dense MXU schedule).  ``row_nnz`` is the built ELL
    bank's longest row, which sizes K; without it K is estimated from the
    sparsity.

    ``quantize=True`` opts the candidate space into the narrow
    value-storage dtypes (int8, and fp8 on TPU backends).  It is opt-in
    because narrow storage is *lossy* — on memory-bound sparse layers the
    roofline all but always prefers the smaller value stream, so a default
    planner run would silently trade accuracy for bandwidth; a plan that
    pins a narrow dtype is an explicit artifact instead.
    """
    interpret = backend != "tpu"
    # The value-dtype axis is backend-capability-filtered up front: a plan
    # must never pin a dtype the backend cannot execute (fp8 off-TPU) —
    # the static verifier flags any such entry as a pre-flight error.
    cands = enumerate_candidates(
        g, value_dtypes=(allowed_value_dtypes(backend) if quantize
                         else ("float32",)), row_nnz=row_nnz)
    if mode == "wall":
        cands = [cd for cd in cands if measurable(cd, backend)]
    if not cands:
        return PlanEntry(method="dense", source="heuristic",
                         provenance="default")
    best, best_t = None, float("inf")
    rng = np.random.default_rng(0)
    x = None
    if mode == "wall":
        if w_dense is None:
            raise ValueError("wall-mode tuning needs the layer's dense weights")
        x = jnp.asarray(rng.standard_normal(
            (g.batch, g.c, g.h, g.w)).astype(np.float32))
    kept_by_block: Dict[Any, float] = {}
    for cd in cands:
        if mode == "wall":
            t = measure_candidate(g, cd, w_dense, x, warmup=warmup,
                                  iters=iters, interpret=interpret)
            # time_fn returns TimingStats: surface the (min, p50, max)
            # spread so a lucky median is visible in the tuning log.
            _LOG.debug(
                "wall %s %s: p50=%.1fus min=%.1fus max=%.1fus", g.name,
                cd, t * 1e6, t.min * 1e6, t.max * 1e6)
        elif cd.method == "bsr" and w_dense is not None:
            # One bank scan per block shape, not per candidate — the
            # ladder has ~4 shapes but ~dozens of (te, fuse) points.
            blk = (cd.block_m or 8, cd.block_n or 128)
            if blk not in kept_by_block:
                kept_by_block[blk] = bcsr_true_kept(w_dense, *blk)
            t = roofline_estimate(g, cd, bsr_kept=kept_by_block[blk])
        else:
            t = roofline_estimate(g, cd)
        if t < best_t:
            best, best_t = cd, t
    if mode == "wall":
        _LOG.info(
            "wall winner %s %s: p50=%.1fus spread=[%.1fus, %.1fus]",
            g.name, best.method, best_t * 1e6,
            getattr(best_t, "min", best_t) * 1e6,
            getattr(best_t, "max", best_t) * 1e6)
    return PlanEntry(method=best.method, tm=best.tm, pad_to=best.pad_to,
                     te=best.te, fuse=best.fuse,
                     pipeline=best.pipeline, permute=best.permute,
                     block_m=best.block_m, block_n=best.block_n,
                     value_dtype=best.value_dtype,
                     est_s=best_t,
                     source="measured" if mode == "wall" else "roofline")


def _fits_bank(pe: PlanEntry, op: ConvOp, row_nnz: int) -> bool:
    """Whether a pallas entry's pinned schedule dispatches for this layer's
    own bank, whose longest row is ``row_nnz``.  Layers sharing a cache key
    share geometry, not row lengths, and the ELL K the entry's ``pad_to``
    rounds that row to sizes the kernel's SMEM tiles."""
    if pe.method != "pallas":
        return True
    sched, _ = resolve_schedule(
        op.m, op.c, op.e, op.f, ell_k(row_nnz, pe.pad_to or 8), op.k, op.k,
        op.stride, tm=pe.tm, te=pe.te,
        fuse_res=pe.fuse and op.res is not None,
        pipeline=pe.pipeline, value_dtype=pe.value_dtype)
    return sched is not None


def weight_structure_tag(w_dense: np.ndarray) -> str:
    """Cache-key component for weights-aware plans: the bank's kept-tile
    fraction at the default (8, 128) block, bucketed to 10%.

    Weights-aware roofline scores depend on the bank's *block structure*
    (a magnitude-pruned and a block-pruned bank of identical geometry and
    sparsity price bsr very differently), so plans scored with weights in
    hand must not share a cache entry across structures — without this
    tag, a block-pruned model's ``bsr`` plan could be inherited by an
    unstructured bank of the same shape, the exact mis-routing the
    weights-aware costing exists to prevent.
    """
    w = np.asarray(w_dense)
    gbn = max(1, -(-(int(np.prod(w.shape[1:]))) // 128))
    frac = bcsr_true_kept(w, 8, 128) / gbn
    return f"bk{min(1.0, round(frac, 1))}"


def plan_program(program: Program, *, batch: int = 1,
                 dtype: str = "float32", mode: str = "roofline",
                 cache: Optional[PlanCache] = None,
                 params: Optional[Dict[str, Any]] = None,
                 backend: str,
                 warmup: int = 1, iters: int = 3,
                 quantize: bool = False,
                 ) -> Dict[str, PlanEntry]:
    """Tune every conv op of a lowered program; returns name -> PlanEntry.

    ``backend`` is the platform the plan will run on — it keys the cache
    and filters the candidates the backend cannot execute.
    Cache hits skip scoring entirely; misses are scored and written back (and
    persisted to ``cache.path`` if set).  Duplicate geometries — same layer
    key, which includes the fused-epilogue signature — are scored once per
    run even with no cache supplied.  ``mode="roofline"`` needs no weights
    but *uses* ``params`` when supplied (bsr candidates are priced from
    each layer's actual kept-block structure); ``mode="wall"`` requires
    them and measures on the pruned weights (as built by ``cnn.init_cnn``
    / ``engine.init_conv_params``).  ``quantize=True`` opts scoring into
    the narrow value-storage dtypes (see :func:`plan_layer`) — quantised
    winners are a deliberate accuracy/bandwidth trade, never a default.
    """
    if mode not in ("roofline", "wall"):
        raise ValueError(f"unknown tuning mode {mode!r}")
    plan: Dict[str, PlanEntry] = {}
    scored: Dict[str, PlanEntry] = {}
    misses = 0
    for op in program.conv_ops:
        g = geometry_of_op(op, batch=batch, dtype=dtype)
        w_dense = row_nnz = None
        if op.sparsity > 0 and params is not None and op.name in params:
            w_dense = np.asarray(params[op.name]["w"])
            # The built bank is what the engine runs: its longest row
            # decides the ELL K every pallas schedule is sized for.
            ell = params[op.name].get("ell")
            if ell is not None:
                row_nnz = int(np.asarray(ell.nnz).max())
        base_key = key = layer_key(g, backend)
        if w_dense is not None:
            # Weights-aware scores depend on the bank's block structure,
            # which the geometry key cannot see: extend the key so e.g. a
            # block-pruned model's bsr plan is never inherited by an
            # unstructured bank of identical geometry.
            key += "_" + weight_structure_tag(w_dense)
        telem = telemetry.is_enabled()
        entry = cache.get(key) if cache is not None else None
        if entry is not None and telem:
            # Entries arrive from load() already marked cache_hit/migrated.
            telemetry.counter(f"tuning.plan.{entry.provenance}").inc()
        if entry is None and cache is not None and key != base_key:
            # Legacy compatibility: pre-tag caches (v1-v4 migrations, or
            # weight-free v5 runs) keyed without the structure tag.  Only
            # bsr pricing is structure-sensitive, so a non-bsr legacy
            # winner is safe to inherit; a legacy bsr entry is not — it
            # may have been priced for a different bank structure.
            legacy = cache.get(base_key)
            if legacy is not None and legacy.method != "bsr":
                entry = dataclasses.replace(legacy, provenance="migrated")
                if telem:
                    telemetry.counter("tuning.plan.legacy_inherit").inc()
            elif legacy is not None and telem:
                # A legacy bsr winner exists but cannot be trusted for this
                # bank structure — the layer re-scores below.
                telemetry.counter("tuning.plan.bsr_structure_rescore").inc()
        if entry is None:
            entry = scored.get(key)
            if entry is not None and telem:
                telemetry.counter("tuning.plan.dedup_hit").inc()
        if (entry is not None and row_nnz is not None
                and not _fits_bank(entry, op, row_nnz)):
            # Scored for a same-geometry bank with shorter rows: re-score
            # this layer on its own weights.
            entry = None
        if entry is None:
            if op.sparsity <= 0:
                # Dense-kept layer: one candidate, nothing to measure.
                entry = PlanEntry(method="dense", source="heuristic",
                                  provenance="default")
            else:
                if mode == "wall" and w_dense is None:
                    raise ValueError(
                        f"wall-mode tuning needs params for {op.name}")
                entry = plan_layer(g, mode=mode, w_dense=w_dense,
                                   row_nnz=row_nnz, backend=backend,
                                   warmup=warmup, iters=iters,
                                   quantize=quantize)
            misses += 1
            scored[key] = entry
            if telem:
                telemetry.counter("tuning.plan.scored").inc()
            if cache is not None:
                cache.put(key, entry)
        plan[op.name] = entry
    if cache is not None and cache.path and misses:
        cache.save()
    return plan


def plan_network(net: Sequence[Any], in_c: int, image: int, *, batch: int = 1,
                 **kw) -> Dict[str, PlanEntry]:
    """Convenience wrapper: lower the spec once, then :func:`plan_program`."""
    program = lower(net, (in_c, image, image))
    return plan_program(program, batch=batch, **kw)


def apply_plan_to_params(params: Dict[str, Any],
                         plan: Dict[str, PlanEntry]) -> Dict[str, Any]:
    """Rebuild per-layer sparse formats at each plan's tuned knobs.

    Stores them under ``ell_auto`` / ``ell2d_auto`` / ``bcsr_auto`` next to
    the defaults, so non-auto methods keep working unchanged.  A pallas
    entry with ``permute=True`` gets its bank nnz-balanced here, host-side,
    so the engine never sorts inside a trace; a ``bsr`` entry gets its
    BCSR bank blocked at the plan's (block_m, block_n) — an entry with no
    block shape (a stale pre-v5 plan) is skipped, and the engine falls
    back to dense for it.  A plan pinning a narrow ``value_dtype`` gets
    its bank quantised here, host-side (per-output-channel symmetric
    scales, values stored int8/fp8), so the engine's traced forward only
    ever streams the narrow bank.  Safe to call repeatedly.
    """
    for name, pe in plan.items():
        entry = params.get(name)
        if entry is None or "ell" not in entry:
            continue  # dense-kept layer: nothing to rebuild
        pad_to = pe.pad_to or 8
        w = np.asarray(entry["w"])
        if pe.method == "lowered":
            entry["ell2d_auto"] = ell_from_dense(
                w.reshape(w.shape[0], -1), pad_to=pad_to)
        elif pe.method in ("csr-direct", "pallas"):
            bank = ell_from_dense_conv(
                w, pad_to=pad_to,
                balance=pe.method == "pallas" and pe.permute)
            if pe.method == "pallas" and pe.value_dtype != "float32":
                bank = quantize_values(bank, pe.value_dtype)
            entry["ell_auto"] = bank
        elif (pe.method == "bsr" and pe.block_m is not None
              and pe.block_n is not None):
            bank = bcsr_conv_from_dense(w, block=(pe.block_m, pe.block_n))
            if pe.value_dtype != "float32":
                bank = quantize_values(bank, pe.value_dtype)
            entry["bcsr_auto"] = bank
    return params


def format_plan(plan: Dict[str, PlanEntry]) -> str:
    """Human-readable per-layer plan table (the paper's customization table)."""
    lines = [f"{'layer':<22} {'method':<11} {'tm':>4} {'te':>4} "
             f"{'pad_to':>6} {'block':>8} {'fuse':>5} {'pipe':>5} {'perm':>5} "
             f"{'vdtype':>8} {'est_us':>10} source"]
    for name, pe in plan.items():
        block = (f"{pe.block_m}x{pe.block_n}"
                 if pe.block_m and pe.block_n else "-")
        vdt = {"float32": "f32", "float8_e4m3fn": "fp8"}.get(
            pe.value_dtype, pe.value_dtype)
        lines.append(
            f"{name:<22} {pe.method:<11} {pe.tm or '-':>4} "
            f"{pe.te or '-':>4} "
            f"{pe.pad_to or '-':>6} {block:>8} {'y' if pe.fuse else '-':>5} "
            f"{'y' if pe.pipeline else '-':>5} "
            f"{'y' if pe.permute else '-':>5} "
            f"{vdt:>8} "
            f"{pe.est_s * 1e6:>10.1f} {pe.source}")
    return "\n".join(lines)
