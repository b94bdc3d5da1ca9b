"""Schedule rules: statically verify kernel dispatch for a lowered net.

Drives the kernels' own pure dispatch probes —
``kernels.sparse_conv.ops.resolve_schedule`` and
``kernels.bsr_conv.ops.resolve_bsr_schedule`` — over every conv op a
network can dispatch, without executing anything.  A plan entry that pins a
method the probe rejects is exactly the configuration that silently falls
back at serving time (the ``repro.telemetry.fallback`` reason codes), so
every such finding is an **error**, mapped through
``diagnostics.REASON_RULES`` to the rule that names the runtime reason.

Without a plan entry, the same probes run as method-space coverage
(severity ``info``): which sparse methods this geometry could ever run.

Rules:

  sched.smem_budget      scalar-prefetched operands (packed ELL indices /
                         BCSR block-column table + aux rows) bust SMEM
  sched.vmem_tiling      no VMEM-feasible tiling (or the plan-pinned one
                         busts the budget, counting the pipeline's second
                         halo buffer and the fused residual tile)
  sched.nondividing_tm   a pinned output-channel tile does not divide M
  sched.pipeline_demoted plan asks for the double-buffered halo DMA but
                         the second halo buffer does not fit -> the kernel
                         silently runs the blocking schedule (warning)
  sched.dtype_policy     geometry dtype outside the bf16-in/f32-accumulate
                         policy the kernels implement
  sched.halo_bounds      a resolved tile's halo window would read past the
                         padded input extent (invariant check)
  sched.value_dtype      pinned value-storage dtype unknown, pinned on a
                         method with no quantised path, or not executable
                         on this backend (fp8 off-TPU) — the dtype policy
                         is ``tuning.space.allowed_value_dtypes``, the same
                         table the planner enumerates from
  sched.value_dtype_mismatch
                         the plan's pinned value dtype disagrees with an
                         already-quantised bound bank — the engine falls
                         back to dense with the ``value_dtype_mismatch``
                         runtime reason rather than silently re-coding the
                         bank
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from repro.analysis.diagnostics import REASON_RULES, Diagnostic
from repro.engine.program import ConvOp, Program
from repro.kernels.window import halo_extent
from repro.kernels.bsr_conv.ops import resolve_bsr_schedule
from repro.kernels.sparse_conv.ops import resolve_schedule
from repro.tuning.planner import geometry_of_op
from repro.tuning.space import VALUE_DTYPES, allowed_value_dtypes

RULES = {
    "sched.smem_budget": (
        "error",
        "scalar-prefetched operands bust the SMEM budget",
    ),
    "sched.vmem_tiling": (
        "error",
        "no VMEM-feasible tiling for this geometry/schedule",
    ),
    "sched.nondividing_tm": (
        "error",
        "pinned output-channel tile does not divide M",
    ),
    "sched.pipeline_demoted": (
        "warning",
        "planned double-buffered halo DMA does not fit; kernel silently "
        "runs the blocking schedule",
    ),
    "sched.dtype_policy": (
        "error",
        "dtype outside the bf16/f32-in, f32-accumulate kernel policy",
    ),
    "sched.halo_bounds": (
        "error",
        "tile halo window reads past the padded input extent",
    ),
    "sched.value_dtype": (
        "error",
        "pinned value-storage dtype unknown, on a method with no quantised "
        "path, or not executable on this backend",
    ),
    "sched.value_dtype_mismatch": (
        "error",
        "plan's pinned value dtype disagrees with the already-quantised "
        "bound bank; the engine silently runs dense",
    ),
}

SUPPORTED_DTYPES = ("float32", "bfloat16", "float16")

# Default BCSR block probed when no plan pins one (engine.DEFAULT_BSR_BLOCK;
# re-declared to keep this module import-light).
_DEFAULT_BLOCK = (8, 128)


def _itemsize(dtype: str) -> int:
    return 2 if dtype in ("bfloat16", "float16") else 4


def _ell_k(
    op: ConvOp,
    pad_to: Optional[int],
    params: Optional[Dict[str, Any]],
    batch: int,
    dtype: str,
) -> int:
    """The padded ELL row length the dispatch would see: the bound bank's
    actual K when params are in hand, else the geometry estimate at the
    plan's pad_to bucket."""
    if params is not None:
        entry = params.get(op.name) or {}
        ell = entry.get("ell_auto") or entry.get("ell")
        if ell is not None:
            return int(ell.k)
    g = geometry_of_op(op, batch=batch, dtype=dtype)
    return g.k_est(pad_to or 8)


def _halo_check(
    op: ConvOp,
    te: int,
    *,
    net: Optional[str],
) -> List[Diagnostic]:
    """Invariant: a resolved tile's halo'd input window must stay inside
    the padded input.  ``resolve_*`` clamps te to e and a tile spans all f
    columns, which bounds the halo by the padded extent — this guards that
    contract."""
    out = []
    hp, wp = op.h + 2 * op.pad, op.w + 2 * op.pad
    if halo_extent(te, op.stride, op.k) > hp or (
        halo_extent(op.f, op.stride, op.k) > wp
    ):
        out.append(
            Diagnostic(
                rule="sched.halo_bounds",
                severity="error",
                message=(
                    f"tile ({te}, {op.f}) halo "
                    f"({halo_extent(te, op.stride, op.k)}x"
                    f"{halo_extent(op.f, op.stride, op.k)}) exceeds padded "
                    f"input {hp}x{wp}"
                ),
                net=net,
                layer=op.name,
            )
        )
    return out


def check_value_dtype(
    entry: Any,
    *,
    backend: str,
    bank_dtype: Optional[str] = None,
    net: Optional[str] = None,
    layer: Optional[str] = None,
    location: Optional[str] = None,
) -> List[Diagnostic]:
    """Value-dtype policy for one pallas/bsr plan entry.

    ``sched.value_dtype``: the pinned dtype is unknown, or the backend the
    entry is keyed for cannot execute it (``allowed_value_dtypes`` — the
    planner's own candidate table, so planner and verifier can never
    disagree about what is runnable).  ``sched.value_dtype_mismatch``: the
    bound bank is already quantised at a *different* dtype than the plan
    pins (``bank_dtype``, when the caller has params in hand) — the exact
    configuration the engine refuses with the ``value_dtype_mismatch``
    runtime fallback.  A f32 bank under a narrow plan is healthy (the
    engine quantises in-trace) and reports nothing.
    """
    out: List[Diagnostic] = []
    vdt = getattr(entry, "value_dtype", None)
    if vdt is None:
        vdt = "float32"
    if vdt not in VALUE_DTYPES:
        out.append(
            Diagnostic(
                rule="sched.value_dtype",
                severity="error",
                message=(
                    f"plan pins unknown value dtype {vdt!r}; one of "
                    f"{VALUE_DTYPES}"
                ),
                net=net,
                layer=layer,
                location=location,
            )
        )
        return out
    allowed = allowed_value_dtypes(backend)
    if vdt not in allowed:
        out.append(
            Diagnostic(
                rule="sched.value_dtype",
                severity="error",
                message=(
                    f"plan pins value dtype {vdt!r} but backend "
                    f"{backend!r} only executes {allowed}; dispatch would "
                    f"run a value stream the hardware cannot stream"
                ),
                net=net,
                layer=layer,
                location=location,
            )
        )
        return out
    if (
        bank_dtype is not None
        and bank_dtype != "float32"
        and bank_dtype != vdt
    ):
        out.append(
            Diagnostic(
                rule="sched.value_dtype_mismatch",
                severity="error",
                message=(
                    f"plan pins value dtype {vdt!r} but the bound bank is "
                    f"already quantised as {bank_dtype!r}; the engine falls "
                    f"back to dense (value_dtype_mismatch) rather than "
                    f"silently re-coding the bank"
                ),
                net=net,
                layer=layer,
                location=location,
            )
        )
    return out


def _bank_dtype(bank: Any) -> Optional[str]:
    """The value-storage dtype of a bound bank (None without one)."""
    if bank is None:
        return None
    if getattr(bank, "scale", None) is None:
        return "float32"
    return bank.value_dtype


def check_pallas_entry(
    op: ConvOp,
    entry: Any,
    *,
    net: Optional[str] = None,
    batch: int = 1,
    dtype: str = "float32",
    backend: str = "cpu",
    params: Optional[Dict[str, Any]] = None,
) -> List[Diagnostic]:
    """Verify one plan entry pinning ``method="pallas"`` dispatches to the
    Pallas kernel (not the silent csr-direct fallback)."""
    out: List[Diagnostic] = []
    bank = None
    if params is not None:
        pentry = params.get(op.name) or {}
        bank = pentry.get("ell_auto") or pentry.get("ell")
    out += check_value_dtype(
        entry, backend=backend, bank_dtype=_bank_dtype(bank), net=net,
        layer=op.name)
    if out:
        return out
    vdt = getattr(entry, "value_dtype", "float32") or "float32"
    k = _ell_k(op, entry.pad_to, params, batch, dtype)
    fuse_res = bool(entry.fuse) and op.res is not None
    sched, reason = resolve_schedule(
        op.m,
        op.c,
        op.e,
        op.f,
        k,
        op.k,
        op.k,
        op.stride,
        n=batch,
        padding=op.pad,
        tm=entry.tm,
        te=entry.te,
        fuse_res=fuse_res,
        pipeline=entry.pipeline,
        value_dtype=vdt,
    )
    if sched is None:
        out.append(
            Diagnostic(
                rule=REASON_RULES[reason],
                severity="error",
                message=(
                    f"plan pins pallas (tm={entry.tm} te={entry.te} "
                    f"pad_to={entry.pad_to} k={k}) but "
                    f"dispatch falls back to csr-direct: {reason}"
                ),
                net=net,
                layer=op.name,
            )
        )
        return out
    if sched.tp is not None:
        # The pointwise schedule stages no halo and has no DMA mode to
        # demote.
        return out
    tm, te, pipeline = sched.tm, sched.te, sched.pipeline
    if entry.pipeline and not pipeline:
        out.append(
            Diagnostic(
                rule="sched.pipeline_demoted",
                severity="warning",
                message=(
                    f"plan asks for the double-buffered halo DMA but the "
                    f"second halo buffer does not fit at (tm={tm}, "
                    f"te={te}); the kernel silently runs the blocking "
                    f"schedule"
                ),
                net=net,
                layer=op.name,
            )
        )
    out += _halo_check(op, te, net=net)
    return out


def check_bsr_entry(
    op: ConvOp,
    entry: Any,
    *,
    net: Optional[str] = None,
    batch: int = 1,
    dtype: str = "float32",
    backend: str = "cpu",
    params: Optional[Dict[str, Any]] = None,
) -> List[Diagnostic]:
    """Verify one plan entry pinning ``method="bsr"`` dispatches to the MXU
    kernel (not the silent dense fallback)."""
    out: List[Diagnostic] = []
    bank = None
    if params is not None:
        pentry = params.get(op.name) or {}
        bank = pentry.get("bcsr_auto")
        if bank is not None and entry.block_m is not None and bank.block != (
            entry.block_m,
            entry.block_n,
        ):
            # Block mismatch: the engine rebuilds an f32 bank from the
            # dense weights, so the prebuilt bank's dtype is irrelevant.
            bank = None
    out += check_value_dtype(
        entry, backend=backend, bank_dtype=_bank_dtype(bank), net=net,
        layer=op.name)
    if out:
        return out
    vdt = getattr(entry, "value_dtype", "float32") or "float32"
    if entry.block_m is None or entry.block_n is None:
        # Stale pre-v5 entry: the engine runs dense with
        # engine_reason="stale_plan_no_block".
        out.append(
            Diagnostic(
                rule="plan.stale_bsr_no_block",
                severity="error",
                message=(
                    "plan pins bsr with no block shape (stale pre-v5 "
                    "entry); the engine silently falls back to dense"
                ),
                net=net,
                layer=op.name,
            )
        )
        return out
    bm, bn = int(entry.block_m), int(entry.block_n)
    g = geometry_of_op(op, batch=batch, dtype=dtype)
    gbm, gbn, _ = g.bsr_grid(bm, bn)
    fuse_res = bool(entry.fuse) and op.res is not None
    sched, reason = resolve_bsr_schedule(
        op.c,
        op.e,
        op.f,
        op.k,
        op.k,
        op.stride,
        bm,
        bn,
        gbm,
        gbn,
        itemsize=_itemsize(dtype),
        te=entry.te,
        fuse_res=fuse_res,
        value_dtype=vdt,
    )
    if sched is None:
        out.append(
            Diagnostic(
                rule=REASON_RULES[reason],
                severity="error",
                message=(
                    f"plan pins bsr (block={bm}x{bn} te={entry.te}) but "
                    f"dispatch falls back to dense: {reason}"
                ),
                net=net,
                layer=op.name,
            )
        )
        return out
    out += _halo_check(op, sched, net=net)
    return out


def _probe_methods(
    op: ConvOp,
    *,
    net: Optional[str],
    batch: int,
    dtype: str,
    params: Optional[Dict[str, Any]],
) -> List[Diagnostic]:
    """Method-space coverage for an unplanned sparse conv: report (info)
    every sparse method this geometry can never dispatch."""
    out: List[Diagnostic] = []
    k = _ell_k(op, None, params, batch, dtype)
    sched, reason = resolve_schedule(
        op.m, op.c, op.e, op.f, k, op.k, op.k, op.stride, n=batch,
        padding=op.pad
    )
    if sched is None:
        out.append(
            Diagnostic(
                rule=REASON_RULES[reason],
                severity="info",
                message=(
                    f"method pallas unavailable for this geometry "
                    f"(k={k}): {reason}"
                ),
                net=net,
                layer=op.name,
            )
        )
    bm, bn = _DEFAULT_BLOCK
    g = geometry_of_op(op, batch=batch, dtype=dtype)
    gbm, gbn, _ = g.bsr_grid(bm, bn)
    sched, reason = resolve_bsr_schedule(
        op.c,
        op.e,
        op.f,
        op.k,
        op.k,
        op.stride,
        bm,
        bn,
        gbm,
        gbn,
        itemsize=_itemsize(dtype),
    )
    if sched is None:
        out.append(
            Diagnostic(
                rule=REASON_RULES[reason],
                severity="info",
                message=(
                    f"method bsr unavailable at the default {bm}x{bn} "
                    f"block: {reason}"
                ),
                net=net,
                layer=op.name,
            )
        )
    return out


def check_network(
    program: Program,
    plan: Optional[Dict[str, Any]] = None,
    *,
    net: Optional[str] = None,
    batch: int = 1,
    dtype: str = "float32",
    backend: str = "cpu",
    params: Optional[Dict[str, Any]] = None,
) -> List[Diagnostic]:
    """Schedule-verify every conv op of a lowered program.

    ``plan`` is a ``{layer_name: PlanEntry}`` table (what ``CnnEngine``
    binds); ops it pins to a Pallas/BCSR method are verified to actually
    dispatch there (error otherwise).  Unplanned sparse ops get
    method-space coverage probes at severity ``info``.
    """
    out: List[Diagnostic] = []
    if dtype not in SUPPORTED_DTYPES:
        out.append(
            Diagnostic(
                rule="sched.dtype_policy",
                severity="error",
                message=(
                    f"dtype {dtype!r} outside the kernel policy "
                    f"{SUPPORTED_DTYPES} (inputs bf16/f16/f32, f32 "
                    f"accumulate)"
                ),
                net=net,
            )
        )
        return out
    for op in program.conv_ops:
        if op.sparsity <= 0:
            continue  # dense-kept layer: only ever runs dense
        entry = (plan or {}).get(op.name)
        if entry is None:
            out += _probe_methods(
                op, net=net, batch=batch, dtype=dtype, params=params
            )
        elif entry.method == "pallas":
            out += check_pallas_entry(
                op,
                entry,
                net=net,
                batch=batch,
                dtype=dtype,
                backend=backend,
                params=params,
            )
        elif entry.method == "bsr":
            out += check_bsr_entry(op, entry, net=net, batch=batch,
                                   dtype=dtype, backend=backend,
                                   params=params)
        elif entry.tm is not None and (entry.tm < 1 or op.m % entry.tm):
            # Non-Pallas methods ignore tm at execution time, but a
            # nondividing tm in the entry signals a stale/mis-keyed plan.
            out.append(
                Diagnostic(
                    rule="sched.nondividing_tm",
                    severity="warning",
                    message=(
                        f"plan entry carries tm={entry.tm} which does not "
                        f"divide m={op.m} (stale or mis-keyed plan?)"
                    ),
                    net=net,
                    layer=op.name,
                )
            )
    return out
