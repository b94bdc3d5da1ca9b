"""CnnEngine: bind params + a tuned plan to a lowered program and execute.

The compile-once executor: a :class:`~repro.engine.program.Program` (from
``lower()``) plus a parameter dict plus an optional tuned plan, executed
through a cached ``jax.jit`` per (method, input geometry, fuse override).
Nothing here walks the nested spec and nothing mutates ``params`` inside a
trace — FC weights are created once at *bind* time from each ``FCOp``'s
statically-resolved fan-in.

Conv epilogues (``bias → ReLU`` and bottleneck ``bias → +shortcut → ReLU``)
were fused into ``ConvOp`` at lowering time; for the Pallas method they are
executed *in-kernel* (one output write from the f32 accumulator instead of
three HBM passes), for the other methods as the same unfused op sequence
the pre-engine executor ran — bit-for-bit compatible.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.direct_conv import dense_conv, direct_sparse_conv
from repro.core.lowering import lowered_sparse_conv
from repro.core.pruning import magnitude_prune
from repro.core.sparse_format import (balance_ell_conv, bcsr_conv_from_dense,
                                      ell_from_dense, ell_from_dense_conv,
                                      quantize_values)
from repro.engine.program import (ConcatOp, ConvOp, FCOp, PoolOp, Program,
                                  ReluOp, ResidualAddOp)
from repro.kernels.bsr_conv.ops import bsr_conv, resolve_bsr_schedule
from repro.kernels.sparse_conv.ops import resolve_schedule
from repro.kernels.sparse_conv.ops import sparse_conv as pallas_sparse_conv
from repro.telemetry.fallback import record_fallback
from repro.telemetry.report import ExecutionReport, OpReport

METHODS = ("dense", "lowered", "csr-direct", "pallas", "bsr", "auto")

# Default BCSR tile shape for a direct ``method="bsr"`` call (no tuned plan
# pinning one); the autotuner picks per layer from the block ladder.
DEFAULT_BSR_BLOCK = (8, 128)


@dataclasses.dataclass
class _Decision:
    """One conv op's resolved dispatch knobs — what the plan (or the
    caller) asked for, before the kernel's own feasibility checks.

    Pure Python over plan entries; shared by ``_conv`` (trace time) and
    ``execution_report`` (no execution), so the report can never disagree
    with what the executor dispatches.
    """

    auto: bool                    # method="auto" (plan-driven) call
    pe: Any                       # the PlanEntry consulted (None without)
    method: str                   # method to execute (pre-kernel-checks)
    method_planned: str           # what the plan/caller asked for
    tm: Optional[int]
    te: Optional[int]
    pipeline: Optional[bool]
    permute: bool
    fuse: bool
    block: Optional[Tuple[int, int]]
    value_dtype: str              # value-storage dtype the kernel streams
    quantize_in_trace: bool       # f32 bank, narrow plan: quantise in-trace
    engine_reason: Optional[str]  # engine-level fallback (stale bsr plan,
                                  # value-dtype mismatch)
    provenance: str


def init_conv_params(program: Program, rng: np.random.Generator,
                     ) -> Dict[str, Any]:
    """Random pruned weights for every conv of a lowered program.

    Draws in ``conv_table`` (historical spec-walk) order, then one integer
    for the FC weight stream, so the result is bit-identical to the
    pre-engine ``init_cnn``.
    """
    params: Dict[str, Any] = {}
    for l, (c, _, _) in program.conv_table:
        w = (rng.standard_normal((l.out_c, c, l.k, l.k))
             .astype(np.float32) * (2.0 / (c * l.k * l.k)) ** 0.5)
        if l.sparsity > 0:
            w = np.asarray(magnitude_prune(jnp.asarray(w), l.sparsity))
        entry = {"w": jnp.asarray(w), "b": jnp.zeros((l.out_c,), jnp.float32)}
        if l.sparsity > 0:
            entry["ell"] = ell_from_dense_conv(w)
            entry["ell2d"] = ell_from_dense(w.reshape(l.out_c, -1))
        params[l.name] = entry
    params["_fc_rng"] = rng.integers(0, 2**31)
    return params


def _op_name(op) -> str:
    """A program op's name: its layer's, else its kind and output slot."""
    return getattr(op, "name", None) or f"{type(op).__name__}:{op.out}"


def _pool(op: PoolOp, x: jax.Array) -> jax.Array:
    if op.kind == "gap":
        return x.mean(axis=(2, 3), keepdims=True)
    init = -jnp.inf if op.kind == "max" else 0.0
    red = jax.lax.max if op.kind == "max" else jax.lax.add
    y = jax.lax.reduce_window(
        x, init, red, (1, 1, op.k, op.k), (1, 1, op.stride, op.stride),
        ((0, 0), (0, 0), (op.pad, op.pad), (op.pad, op.pad)))
    if op.kind == "avg":
        y = y / (op.k * op.k)
    return y


class CnnEngine:
    """Program + params (+ plan) -> cached-jit executor.

    ``engine(x, method=...)`` compiles once per (method, input shape/dtype,
    fuse override) and replays the compiled program afterwards.  ``plan``
    is a ``{layer_name: PlanEntry}`` table from ``repro.tuning``; with
    ``method="auto"`` and no plan bound, a roofline-mode plan is computed
    per batch size on first use.

    ``fuse=None`` (default) fuses the Pallas epilogue in-kernel (and honors
    each plan entry's ``fuse`` flag under ``method="auto"``); ``fuse=False``
    forces the unfused three-pass epilogue — the benchmark baseline.
    Plan entries' ``pipeline`` (double-buffered halo DMA) and ``permute``
    (nnz-balanced bank) flags are honored under ``method="auto"``; plain
    ``method="pallas"`` lets ``ops.sparse_conv`` auto-enable the pipeline
    whenever the second halo buffer fits VMEM.

    ``method="bsr"`` runs the BCSR MXU conv kernel: a plan entry's
    ``(block_m, block_n)`` picks the tile shape (``DEFAULT_BSR_BLOCK`` for
    direct calls); banks not prebuilt by ``apply_plan_to_params`` are
    blocked from the bound dense weights at trace time.  A stale plan entry
    claiming ``bsr`` with no block shape (pre-v5 cache) falls back to the
    dense executor.

    ``strict=True`` runs the pre-flight static verifier at bind time
    (``repro.analysis``): the lowered program is structurally checked and
    every plan-pinned Pallas/BCSR schedule is verified to actually
    dispatch — a configuration that would silently fall back at serving
    time raises :class:`repro.analysis.PreflightError` here instead.

    The engine runs on JAX's default device, read once at bind: the Pallas
    kernels compile for it when it is a TPU and run in interpret mode on
    any other platform — never interpreted on a TPU.
    """

    def __init__(self, program: Program, params: Dict[str, Any],
                 plan: Optional[Dict[str, Any]] = None, *,
                 strict: bool = False):
        self.program = program
        self.params = params
        self.plan = plan
        self.platform = jax.devices()[0].platform
        self.interpret = self.platform != "tpu"
        if strict:
            # Lazy import: repro.analysis imports this module's kernel deps.
            from repro.analysis import PreflightError
            from repro.analysis.checker import preflight
            diags = preflight(program, plan, params)
            errors = [d for d in diags if d.severity == "error"]
            if errors:
                raise PreflightError(errors)
        self.fc_weights = self._bind_fc(program, params)
        self._fns: Dict[Any, Any] = {}
        self._auto_plans: Dict[int, Dict[str, Any]] = {}
        # Trace-built BCSR banks, keyed (layer, block): params are never
        # mutated (their leaf identities fingerprint the engine memo), so
        # banks built for a plan block that differs from the prebuilt one
        # are cached here instead of rebuilt every trace/report.
        self._bcc_cache: Dict[Any, Any] = {}
        # Mean kept tiles per block-row of each BCSR bank, keyed (layer,
        # block): read to the host once, for the report's cost attribution.
        self._bsr_kept: Dict[Any, float] = {}
        # One ExecutionReport per compiled function (its memo key) and
        # rung: the dispatch decisions are static per compiled function.
        self._reports: Dict[Any, ExecutionReport] = {}
        # The ExecutionReport of the most recent telemetry-enabled forward.
        self.last_report: Optional[ExecutionReport] = None

    # -- bind -------------------------------------------------------------

    @staticmethod
    def _bind_fc(program: Program, params: Dict[str, Any],
                 ) -> Dict[Any, np.ndarray]:
        """FC weights, created here (not by mutating ``params`` mid-trace).

        Keyed on ``(name, in_f)`` and drawn in program order from the
        ``_fc_rng`` seed — the same stream positions the historical lazy
        creation used on a fresh params dict, so two engines bound at
        different image sizes get identical weights for every FC layer
        whose fan-in agrees, and can never collide when fan-ins differ.
        """
        rng = np.random.default_rng(int(params.get("_fc_rng", 0)))
        out: Dict[Any, np.ndarray] = {}
        for op in program.fc_ops:
            out[(op.name, op.in_f)] = (
                rng.standard_normal((op.in_f, op.out_f))
                .astype(np.float32) * (1.0 / op.in_f) ** 0.5)
        return out

    def _auto_plan(self, batch: int) -> Dict[str, Any]:
        plan = self._auto_plans.get(batch)
        if plan is None:
            from repro.tuning.planner import plan_program  # lazy: avoids cycle
            # Pass the bound params: roofline mode then prices bsr
            # candidates from each layer's *actual* kept-block structure
            # (unstructured banks keep nearly every tile and must not be
            # routed to the MXU path on the block-pruned estimate).
            plan = plan_program(self.program, batch=batch, mode="roofline",
                                params=self.params, backend=self.platform)
            self._auto_plans[batch] = plan
        return plan

    # -- dispatch decisions ------------------------------------------------

    def _plan_decision(self, op: ConvOp, method: str, plan,
                       fuse_override: Optional[bool]) -> _Decision:
        """Resolve one conv op's dispatch knobs from the plan (or the
        caller's direct method) — the pure-Python half of ``_conv``."""
        auto = method == "auto"
        tm = te = None
        pipeline = None  # ops.sparse_conv auto-picks when the 2nd halo fits
        permute = False
        block = None     # bsr: None = any prebuilt bank (or the default)
        fuse = True if fuse_override is None else fuse_override
        pe = None
        engine_reason = None
        provenance = "direct"
        if auto:
            pe = (plan or {}).get(op.name)
            method = pe.method if pe is not None else "dense"
            provenance = pe.provenance if pe is not None else "default"
            if pe is not None:
                tm, te = pe.tm, pe.te
                pipeline, permute = pe.pipeline, pe.permute
                if fuse_override is None:
                    fuse = pe.fuse
                if method == "bsr":
                    if pe.block_m is None or pe.block_n is None:
                        # Stale plan predating the v5 schema: no block
                        # shape to run — fall back to the dense executor.
                        method = "dense"
                        engine_reason = "stale_plan_no_block"
                    else:
                        block = (pe.block_m, pe.block_n)
        method_planned = pe.method if (auto and pe is not None) else (
            "dense" if auto else method)
        value_dtype = "float32"
        quantize_in_trace = False
        if auto and pe is not None and method in ("pallas", "bsr"):
            # Value-dtype resolution: what the plan pinned vs what the bound
            # bank stores.  Match -> run the bank as-is.  f32 bank + narrow
            # plan (apply_plan_to_params not run) -> quantise in-trace, the
            # same per-channel symmetric construction it would have built
            # host-side.  Any other mismatch — a migrated (f32) entry
            # against an already-quantised bank, or two different narrow
            # dtypes — is a stale plan: the entry was scored for a value
            # stream the params no longer carry, so fall back to dense and
            # say so rather than silently dequantising.
            want = pe.value_dtype
            entry = self.params.get(op.name, {})
            if method == "pallas":
                bank = entry.get("ell_auto", entry.get("ell"))
            else:
                bank = entry.get("bcsr_auto")
                if bank is not None and not (block is None
                                             or bank.block == block):
                    bank = None  # _bcsr_for rebuilds f32 from dense weights
            have = ("float32" if bank is None or bank.scale is None
                    else bank.value_dtype)
            if want == have:
                value_dtype = want
            elif have == "float32":
                value_dtype = want
                quantize_in_trace = True
            else:
                method = "dense"
                engine_reason = "value_dtype_mismatch"
        return _Decision(auto=auto, pe=pe, method=method,
                         method_planned=method_planned, tm=tm, te=te,
                         pipeline=pipeline, permute=permute, fuse=fuse,
                         block=block, value_dtype=value_dtype,
                         quantize_in_trace=quantize_in_trace,
                         engine_reason=engine_reason,
                         provenance=provenance)

    def _bcsr_for(self, op: ConvOp, entry: Dict[str, Any], block):
        """The BCSR bank this op runs: the prebuilt ``bcsr_auto`` when its
        block matches, else one blocked from the bound dense weights —
        built host-side once per (layer, block) and cached on the engine.
        The build is evaluated eagerly even when called inside a trace: the
        cached bank outlives the trace, so it must hold concrete arrays, not
        the tracer a staged ``jnp.asarray`` would give (a later retrace of
        another method or of ``lowered`` would otherwise read a leaked
        tracer)."""
        bcc = entry.get("bcsr_auto")
        if bcc is not None and (block is None or bcc.block == block):
            return bcc
        key = (op.name, block or DEFAULT_BSR_BLOCK)
        bcc = self._bcc_cache.get(key)
        if bcc is None:
            with jax.ensure_compile_time_eval():
                bcc = bcsr_conv_from_dense(np.asarray(entry["w"]),
                                           block=block or DEFAULT_BSR_BLOCK)
            self._bcc_cache[key] = bcc
        return bcc

    # -- execute ----------------------------------------------------------

    def _conv(self, op: ConvOp, x: jax.Array, res: Optional[jax.Array],
              method: str, plan, fuse_override: Optional[bool]) -> jax.Array:
        entry = self.params[op.name]
        d = self._plan_decision(op, method, plan, fuse_override)
        method, fuse = d.method, d.fuse
        tm, te, pipeline = d.tm, d.te, d.pipeline
        if d.auto:
            ell = entry.get("ell_auto", entry.get("ell"))
            ell2d = entry.get("ell2d_auto", entry.get("ell2d"))
            if (d.permute and method == "pallas" and ell is not None
                    and ell.perm is None):
                # Plan wants the nnz-balanced bank but the params carry a
                # natural-order one (apply_plan_to_params not run): balance
                # in-trace — pure gathers, jit-safe.
                ell = balance_ell_conv(ell)
            if (d.quantize_in_trace and method == "pallas"
                    and ell is not None):
                # Plan pinned a narrow value dtype but the params carry the
                # f32 bank (apply_plan_to_params not run): quantise
                # in-trace — pure jnp, jit-safe, identical to the
                # host-side construction.
                ell = quantize_values(ell, d.value_dtype)
        else:
            ell, ell2d = entry.get("ell"), entry.get("ell2d")
        if d.engine_reason is not None:
            # Engine-level silent degradation (stale bsr plan): report it
            # like the kernels report theirs — this runs at trace time.
            record_fallback(
                "engine", d.engine_reason, layer=op.name,
                geometry=f"m={op.m} c={op.c} e={op.e} f={op.f}",
                fallback_to="dense")
        bcc = None
        if method == "bsr" and op.sparsity > 0:
            bcc = self._bcsr_for(op, entry, d.block)
            if d.quantize_in_trace and bcc.scale is None:
                bcc = quantize_values(bcc, d.value_dtype)
        b = entry["b"]
        if op.sparsity == 0 or method == "dense":
            y = dense_conv(x, entry["w"], stride=op.stride, padding=op.pad)
        elif method == "lowered":
            y = lowered_sparse_conv(x, ell2d, op.k, op.k,
                                    stride=op.stride, padding=op.pad)
        elif method == "csr-direct":
            y = direct_sparse_conv(x, ell, stride=op.stride, padding=op.pad)
        elif method == "pallas":
            interp = self.interpret
            if fuse:
                return pallas_sparse_conv(
                    x, ell, stride=op.stride, padding=op.pad, tm=tm, te=te,
                    bias=b, fuse_relu=op.fuse_relu, residual=res,
                    pipeline=pipeline, interpret=interp, layer=op.name)
            y = pallas_sparse_conv(x, ell, stride=op.stride, padding=op.pad,
                                   tm=tm, te=te, pipeline=pipeline,
                                   interpret=interp, layer=op.name)
        elif method == "bsr":
            interp = self.interpret
            if fuse:
                return bsr_conv(
                    x, bcc, stride=op.stride, padding=op.pad, te=te,
                    bias=b, fuse_relu=op.fuse_relu, residual=res,
                    interpret=interp, layer=op.name)
            y = bsr_conv(x, bcc, stride=op.stride, padding=op.pad, te=te,
                         interpret=interp, layer=op.name)
        else:
            raise ValueError(method)
        # Unfused epilogue: the exact op sequence of the pre-engine executor.
        y = y + b[None, :, None, None]
        if res is not None:
            y = y + res
        if op.fuse_relu:
            y = jax.nn.relu(y)
        return y

    def _exec_op(self, op, vals: Dict[int, jax.Array], method: str, plan,
                 fuse_override: Optional[bool]) -> jax.Array:
        """Execute one program op against the value table."""
        if isinstance(op, ConvOp):
            res = vals[op.res] if op.res is not None else None
            return self._conv(op, vals[op.src], res, method, plan,
                              fuse_override)
        if isinstance(op, ReluOp):
            return jax.nn.relu(vals[op.src])
        if isinstance(op, PoolOp):
            return _pool(op, vals[op.src])
        if isinstance(op, ConcatOp):
            return jnp.concatenate([vals[s] for s in op.srcs], axis=1)
        if isinstance(op, ResidualAddOp):
            y = vals[op.a] + vals[op.b]
            return jax.nn.relu(y) if op.fuse_relu else y
        if isinstance(op, FCOp):
            flat = vals[op.src].reshape(vals[op.src].shape[0], -1)
            return flat @ self.fc_weights[(op.name, op.in_f)]
        raise TypeError(f"unknown op {op!r}")

    def _execute(self, x: jax.Array, *, method: str, plan,
                 fuse_override: Optional[bool]) -> jax.Array:
        """The traced forward.  Each op runs under a ``jax.named_scope`` of
        its layer, so the compiled program's operations carry the layer in
        their ``op_name`` metadata; the Pallas custom calls keep their
        kernel's name (``sparse_conv_pallas.<n>``, ``bsr_conv_pallas.<n>``)."""
        vals: Dict[int, jax.Array] = {0: x}
        for op in self.program.ops:
            with jax.named_scope(_op_name(op)):
                vals[op.out] = self._exec_op(op, vals, method, plan,
                                             fuse_override)
        return vals[self.program.out]

    def __call__(self, x: jax.Array, method: str = "dense", *,
                 fuse: Optional[bool] = None,
                 plan_override: Optional[Dict[str, Any]] = None,
                 rung: Optional[str] = None) -> jax.Array:
        """Execute the bound program.

        ``plan_override`` substitutes an alternate plan table for this call
        without rebinding the engine — the degraded-plan resolution the
        serving tier's ladder uses (``repro.serving.robust``): each rung is
        its own persistent plan dict, so each (method, shape, rung plan)
        still compiles exactly once.  ``rung`` is a label recorded on the
        forward's :class:`ExecutionReport` naming the ladder rung executed.

        The whole call (memo lookup, the report when telemetry is on, and
        the enqueue of the compiled forward) is the span
        ``engine.dispatch``; it returns before the device finishes.
        """
        with telemetry.span("engine.dispatch"):
            fn, plan, key, jit_hit = self._jitted(x, method, fuse,
                                                  plan_override)
            if telemetry.is_enabled():
                # Dispatch-time observation: the report is built from the
                # same _plan_decision the trace uses, never from inside
                # the jit.
                self._record_forward(key, method, plan, fuse, jit_hit,
                                     rung=rung)
            return fn(x)

    def _plan_for(self, method: str, batch: int,
                  plan_override: Optional[Dict[str, Any]]):
        """The plan a call runs: the override, else the bound plan, else
        (``auto`` only) the roofline plan for this batch size."""
        if method not in METHODS:
            raise ValueError(f"unknown method {method!r}; one of {METHODS}")
        plan = plan_override if plan_override is not None else self.plan
        if method == "auto" and plan is None:
            plan = self._auto_plan(batch)
        return plan

    def _jitted(self, x, method: str, fuse: Optional[bool],
                plan_override: Optional[Dict[str, Any]]):
        """(jitted forward, resolved plan, memo key, memo hit) for one call
        shape."""
        plan = self._plan_for(method, int(x.shape[0]), plan_override)
        key = (method, tuple(x.shape), str(x.dtype), fuse, id(plan))
        fn = self._fns.get(key)
        jit_hit = fn is not None
        if fn is None:
            fn = jax.jit(functools.partial(
                self._execute, method=method, plan=plan, fuse_override=fuse))
            self._fns[key] = fn
        return fn, plan, key, jit_hit

    def lowered(self, x, method: str = "dense", *,
                fuse: Optional[bool] = None,
                plan_override: Optional[Dict[str, Any]] = None):
        """The ``jax.stages.Lowered`` forward :meth:`__call__` would run for
        ``x`` (an array or a ``ShapeDtypeStruct``) — ``.compile()`` then
        gives the device program, e.g. to count its Pallas custom calls."""
        fn = self._jitted(x, method, fuse, plan_override)[0]
        return fn.lower(x)

    # -- observability -----------------------------------------------------

    def _record_forward(self, key, method: str, plan,
                        fuse_override: Optional[bool], jit_hit: bool,
                        rung: Optional[str] = None) -> None:
        """Count one forward of the compiled function ``key`` and leave its
        report on ``last_report``.  The report is built on the first
        telemetry-enabled forward of each (compiled function, rung) and
        reused after; only ``jit_cache_hit`` is set per call."""
        report = self._reports.get(key + (rung,))
        if report is None:
            _, shape, dtype, _, _ = key
            report = self._build_report(shape, dtype, method, plan,
                                        fuse_override, None, rung=rung)
            self._reports[key + (rung,)] = report
        report = dataclasses.replace(report, jit_cache_hit=jit_hit)
        self.last_report = report
        telemetry.counter("engine.forwards").inc()
        telemetry.counter(
            "engine.jit_hits" if jit_hit else "engine.jit_misses").inc()
        if report.fallback_count:
            telemetry.counter("engine.fallback_ops").inc(
                report.fallback_count)

    def _build_report(self, shape, dtype: str, method: str, plan,
                      fuse_override: Optional[bool],
                      jit_hit: Optional[bool],
                      rung: Optional[str] = None) -> ExecutionReport:
        batch = int(shape[0])
        report = ExecutionReport(
            method=method, batch=batch, in_shape=tuple(shape), dtype=dtype,
            jit_cache_hit=jit_hit, plan_bound=self.plan is not None,
            rung=rung)
        for op in self.program.conv_ops:
            report.ops.append(self._op_report(op, method, plan,
                                              fuse_override, batch=batch,
                                              dtype=dtype))
        return report

    def _op_report(self, op: ConvOp, method: str, plan,
                   fuse_override: Optional[bool], *, batch: int,
                   dtype: str) -> OpReport:
        """One conv op's OpReport: the dispatch decision (including the
        kernels' own feasibility checks, via their ``resolve_*`` probes)
        plus the roofline attribution of the *executed* schedule."""
        # Lazy: repro.tuning imports this module's kernel deps.
        from repro.tuning.measure import candidate_cost
        from repro.tuning.planner import geometry_of_op
        from repro.tuning.space import Candidate

        entry = self.params[op.name]
        d = self._plan_decision(op, method, plan, fuse_override)
        g = geometry_of_op(op, batch=batch, dtype=dtype)
        executed = "dense" if op.sparsity == 0 else d.method
        reason = d.engine_reason
        pad_to = d.pe.pad_to if d.pe is not None else None
        fuse_res = d.fuse and op.res is not None
        tiling: Dict[str, Any] = {}
        if executed == "pallas":
            ell = (entry.get("ell_auto", entry.get("ell")) if d.auto
                   else entry.get("ell"))
            k = ell.k if ell is not None else g.k_est(pad_to or 8)
            sched, kreason = resolve_schedule(
                op.m, op.c, op.e, op.f, k, op.k, op.k, op.stride, n=batch,
                padding=op.pad, tm=d.tm, te=d.te, fuse_res=fuse_res,
                pipeline=d.pipeline, value_dtype=d.value_dtype)
            if sched is None:
                reason, executed = kreason, "csr-direct"
            else:
                tiling = sched.tiling()
        elif executed == "bsr":
            bcc = self._bcsr_for(op, entry, d.block)
            gbm, kb, bm, bn = bcc.blocks.shape
            itemsize = 2 if dtype in ("bfloat16", "float16") else 4
            sched, kreason = resolve_bsr_schedule(
                op.c, op.e, op.f, op.k, op.k, op.stride, bm, bn, gbm, kb,
                itemsize=itemsize, te=d.te, fuse_res=fuse_res,
                value_dtype=d.value_dtype)
            if sched is None:
                reason, executed = kreason, "dense"
            else:
                tiling = {"te": sched, "block_m": bm, "block_n": bn}
        # Attribute cost at the schedule that actually runs — a fallback op
        # is charged for its fallback path, not the method it asked for.
        vdtype = d.value_dtype if executed in ("pallas", "bsr") else "float32"
        cand = Candidate(
            method=executed, tm=tiling.get("tm"), pad_to=pad_to,
            te=tiling.get("te"),
            fuse=d.fuse if executed in ("pallas", "bsr") else False,
            pipeline=bool(tiling.get("pipeline", False)),
            permute=d.permute if executed == "pallas" else False,
            block_m=tiling.get("block_m"), block_n=tiling.get("block_n"),
            value_dtype=vdtype)
        kept = self._kept_tiles(op, bcc) if executed == "bsr" else None
        cost = candidate_cost(g, cand, bsr_kept=kept)
        return OpReport(
            name=op.name, method_planned=d.method_planned,
            method_executed=executed, provenance=d.provenance,
            plan_source=d.pe.source if d.pe is not None else "-",
            fallback_reason=reason, fuse=d.fuse, tiling=tiling,
            sparsity=op.sparsity, value_dtype=vdtype, **cost)

    def _kept_tiles(self, op: ConvOp, bcc) -> float:
        """Mean kept tiles per block-row of the bank ``bcc`` runs, at least
        1 — what ``tuning.measure.bcsr_true_kept`` computes from the dense
        weights — read from the bank's ``nblocks`` once per (layer,
        block)."""
        key = (op.name, bcc.block)
        kept = self._bsr_kept.get(key)
        if kept is None:
            kept = max(1.0, float(np.asarray(bcc.nblocks).mean()))
            self._bsr_kept[key] = kept
        return kept

    def execution_report(self, x, method: str = "auto", *,
                         fuse: Optional[bool] = None,
                         plan_override: Optional[Dict[str, Any]] = None,
                         rung: Optional[str] = None) -> ExecutionReport:
        """The ExecutionReport a forward with these arguments would produce,
        built without executing anything.

        ``x`` is the input array or just its shape tuple — dispatch is
        static Python over shapes and plan entries, so the report needs
        neither data nor a compile.  ``jit_cache_hit`` reflects whether the
        corresponding compiled function already exists.  ``plan_override``
        and ``rung`` mirror :meth:`__call__` — the serving ladder probes
        each rung's dispatch health through this before routing traffic at
        it.
        """
        shape = tuple(x.shape) if hasattr(x, "shape") else tuple(x)
        dtype = str(x.dtype) if hasattr(x, "dtype") else "float32"
        plan = self._plan_for(method, int(shape[0]), plan_override)
        key = (method, shape, dtype, fuse, id(plan))
        return self._build_report(shape, dtype, method, plan, fuse,
                                  jit_hit=key in self._fns, rung=rung)

    def forward_timed(self, x: jax.Array, method: str = "auto", *,
                      fuse: Optional[bool] = None) -> jax.Array:
        """Opt-in timed mode: execute op-by-op with ``block_until_ready``
        at every op boundary, each op a span ``engine.timed.<layer>``
        (``repro.telemetry.span``: a profiler annotation, a histogram and
        a wall-lane event), so profiles map back to layer names.

        The boundaries defeat whole-program fusion and force a host sync
        per op, so this is a profiling tool, not a serving path — expect
        it to be slower than ``engine(x, ...)``.  Calling it is the opt-in;
        it records regardless of the global telemetry flag and leaves the
        measured report on ``self.last_report`` (``wall_s`` filled for
        every conv).
        """
        plan = self._plan_for(method, int(x.shape[0]), None)
        report = self._build_report(tuple(x.shape), str(x.dtype), method,
                                    plan, fuse, jit_hit=None)
        report.timed = True
        walls: Dict[str, float] = {}
        vals: Dict[int, jax.Array] = {0: x}
        with telemetry.enabled():
            for op in self.program.ops:
                t0 = time.perf_counter()
                with telemetry.span(f"engine.timed.{_op_name(op)}",
                                    kind=type(op).__name__):
                    vals[op.out] = self._exec_op(op, vals, method, plan,
                                                 fuse)
                    jax.block_until_ready(vals[op.out])
                if isinstance(op, ConvOp):
                    walls[op.name] = time.perf_counter() - t0
        for o in report.ops:
            o.wall_s = walls.get(o.name)
        self.last_report = report
        return vals[self.program.out]
