"""Machine-readable sparse-conv perf record: ``BENCH_sparse_conv.json``.

The paper figures print human tables; this module emits the *trajectory*
artifact — a JSON document regenerated by every PR so the sparse-conv hot
path's numbers are diffable PR-over-PR:

  per sparse CONV layer (full 224px geometry):
    kernel_ms_roofline  -- analytic max(compute, memory) kernel time under
                           the blocking and the double-buffered (pipelined)
                           halo DMA schedule, at that layer's preferred
                           (tm, te) tiling — plus a ``bsr`` schedule
                           row: the BCSR MXU conv at the layer's best
                           block shape + spatial tiling (the MXU-vs-VPU
                           crossover, priced per layer)
    staged_stall_ms     -- seconds the VPU stalls on staged-input DMA under
                           each schedule (`tuning.staging_stall_s`): the
                           full staging time when blocking, only the
                           unhidden excess when pipelined — the pipeline's
                           whole point is that this column collapses
    auto_roofline       -- the roofline-mode autotuner's winning method for
                           the layer (the full candidate space: dense /
                           lowered / csr-direct / pallas / bsr, each at
                           every allowed value-storage dtype), with the
                           BCSR block shape when the MXU path wins and the
                           winning ``value_dtype``
    quantised twins     -- every schedule row has ``<name>_int8`` and
                           ``<name>_fp8`` siblings: the same schedule with
                           the bank's value stream stored narrow (per-
                           output-channel f32 scales, f32 accumulation).
                           Each row carries ``hbm_mb``; the emit invariant
                           requires every int8 row to move *strictly*
                           fewer HBM bytes than its f32 twin
    wall_ms_direct      -- measured wall time of the pure-JAX direct path at
                           the reduced CPU benchmark scale (for the record;
                           CPU wall-clock does not reproduce accelerator
                           magnitudes)
  per network:
    end_to_end_ms       -- engine forward wall time (dense and csr-direct)
                           at the reduced scale

Invariants checked at emit time: every layer's pipelined staged-input stall
is <= its blocking stall, strictly smaller whenever the blocking stall is
nonzero (the acceptance bar for the pipelined schedule's roofline credit);
at least one moderate-sparsity layer's ``auto_roofline`` picks the ``bsr``
MXU path (the acceptance bar for the BCSR crossover); and no tuned layer
would silently fall back at dispatch — each record carries a ``fallback``
field (null, or the ``telemetry.fallback`` reason code its plan would hit)
and the run fails if any is non-null.  Wall rows carry their measurement
spread (``wall_ms_direct`` is the p50; ``_min``/``_max`` bracket it).

Each network also carries a ``robustness`` section: the fault-tolerant
serving tier (``repro.serving.robust``) serves one seeded arrival trace
with and without the seeded chaos harness on a virtual clock, recording
p50/p99 latency, retries, and degradation events for both runs.  Emit
invariants: zero lost requests in both runs, no degradation without
injected faults, and degradation evidence (a ladder step-down or a
dropped rung) under them.

  PYTHONPATH=src python -m benchmarks.run bench     # writes the JSON + CSV
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.core import direct_sparse_conv
from repro.kernels.bsr_conv.ops import resolve_bsr_schedule
from repro.kernels.sparse_conv.ops import resolve_schedule, tile_candidates
from repro.models import cnn
from repro.tuning import (Candidate, ConvGeometry, enumerate_candidates,
                          plan_layer, roofline_estimate, staged_input_bytes,
                          staging_stall_s)
from repro.tuning.measure import candidate_cost

# The quantised twin rows emitted beside every f32 schedule row; the fp8
# rows are roofline *projections* (the analytic model prices TPU
# constants, where fp8 value streams execute) — int8 rows are executable
# everywhere.
QUANT_ROWS = (("int8", "int8"), ("fp8", "float8_e4m3fn"))

DEFAULT_PATH = pathlib.Path(__file__).resolve().parents[1] / "BENCH_sparse_conv.json"

# Reduced CPU wall-clock scale (image, batch) per network — fig8's table.
SCALES = {"alexnet": (99, 1), "googlenet": (96, 1), "resnet50": (96, 1)}
ROOFLINE_IMAGE = 224   # analytic rows use the paper's full geometry


def layer_geometry(layer, c: int, h: int, w: int,
                   batch: int = 1) -> ConvGeometry:
    """Tuning geometry for one conv spec at a concrete input shape (shared
    with fig10's staging rows so both reports price the same layers)."""
    return ConvGeometry(
        name=layer.name, m=layer.out_c, c=c, h=h, w=w, r=layer.k, s=layer.k,
        stride=layer.stride, pad=layer.pad, sparsity=layer.sparsity,
        batch=batch)


def _schedule_record(g: ConvGeometry, cand: Candidate) -> Dict[str, float]:
    return {
        "kernel_ms_roofline": roofline_estimate(g, cand) * 1e3,
        "staged_stall_ms": staging_stall_s(g, cand) * 1e3,
        "staged_input_mb": staged_input_bytes(g, cand) / 2**20,
        "hbm_mb": candidate_cost(g, cand)["hbm_bytes"] / 2**20,
    }


def _with_quantised_rows(g: ConvGeometry, schedules: Dict[str, Any],
                         base: str, cand: Candidate) -> None:
    """Emit ``<base>_int8`` / ``<base>_fp8`` twins of one schedule row: the
    identical schedule with the value stream stored narrow (scale row
    charged).  Mutates ``schedules`` in place."""
    for suffix, vdt in QUANT_ROWS:
        schedules[f"{base}_{suffix}"] = _schedule_record(
            g, dataclasses.replace(cand, value_dtype=vdt))


def best_bsr_candidate(g: ConvGeometry) -> Optional[Candidate]:
    """The roofline-best feasible *unfused* BCSR candidate for one layer —
    the same enumeration the tuner uses (`enumerate_candidates` with a
    method filter), restricted to fuse=False so the row is directly
    comparable to the unfused blocking/pipelined ELL rows beside it, and
    to the f32 value stream so the quantised twin rows derive from it.
    None when no block shape fits."""
    cands = [c for c in enumerate_candidates(g, methods=("bsr",),
                                             value_dtypes=("float32",))
             if c.method == "bsr" and not c.fuse]
    if not cands:
        return None
    return min(cands, key=lambda c: roofline_estimate(g, c))


def resolve_plan_fallback(g: ConvGeometry, pe) -> Optional[str]:
    """What the tuned plan would do at dispatch: None when the planned
    kernel's schedule resolves, else the machine-readable reason the engine
    would silently fall back (``telemetry.fallback`` reason codes).

    Uses the same resolvers the kernels dispatch through, at the same
    worst-case geometry the engine sees (pallas: the pad_to-padded ELL row
    length estimate; bsr: every block-column kept)."""
    if pe.method == "pallas":
        k = g.k_est(pe.pad_to or 8)
        _, reason = resolve_schedule(
            g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride,
            tm=pe.tm, te=pe.te,
            fuse_res=pe.fuse and g.residual, pipeline=pe.pipeline,
            value_dtype=pe.value_dtype)
        return reason
    if pe.method == "bsr":
        if pe.block_m is None or pe.block_n is None:
            return "stale_plan_no_block"
        gbm, gbn, _ = g.bsr_grid(pe.block_m, pe.block_n)
        _, reason = resolve_bsr_schedule(
            g.c, g.e, g.f, g.r, g.s, g.stride,
            pe.block_m, pe.block_n, gbm, gbn,
            te=pe.te, fuse_res=pe.fuse and g.residual,
            value_dtype=pe.value_dtype)
        return reason
    return None  # dense / lowered / csr-direct never fall back


def layer_record(g: ConvGeometry) -> Optional[Dict[str, Any]]:
    """Blocking + pipelined + bsr schedule rows for one sparse conv layer:
    the ELL rows at the preferred VMEM-feasible tiling that also fits the
    second halo buffer (falling back to a blocking-only tiling when none
    does), the bsr row at the layer's roofline-best block shape, and the
    roofline autotuner's overall winner (the MXU-vs-VPU crossover)."""
    k = g.k_est(8)
    tiles = tile_candidates(g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride,
                            pipeline=True)
    pipelined_ok = bool(tiles)
    if not tiles:
        tiles = tile_candidates(g.m, g.c, g.e, g.f, k, g.r, g.s, g.stride)
    if not tiles:
        return None  # no Pallas tiling at all: layer runs the fallback
    tm, te = tiles[0]
    base = dict(tm=tm, pad_to=8, te=te)
    rec: Dict[str, Any] = {
        "name": g.name, "m": g.m, "c": g.c, "h": g.h, "w": g.w, "r": g.r,
        "stride": g.stride, "pad": g.pad, "sparsity": g.sparsity,
        "tiling": {"tm": tm, "te": te},
        "schedules": {
            "blocking": _schedule_record(g, Candidate("pallas", **base)),
        },
    }
    _with_quantised_rows(g, rec["schedules"], "blocking",
                         Candidate("pallas", **base))
    if pipelined_ok:
        pcand = Candidate("pallas", **base, pipeline=True)
        rec["schedules"]["pipelined"] = _schedule_record(g, pcand)
        _with_quantised_rows(g, rec["schedules"], "pipelined", pcand)
    bsr = best_bsr_candidate(g)
    if bsr is not None:
        brec = _schedule_record(g, bsr)
        brec["block"] = [bsr.block_m, bsr.block_n]
        brec["te"] = bsr.te
        rec["schedules"]["bsr"] = brec
        _with_quantised_rows(g, rec["schedules"], "bsr", bsr)
    pe = plan_layer(g, mode="roofline", quantize=True,
                    backend=jax.devices()[0].platform)
    rec["auto_roofline"] = {
        "method": pe.method, "kernel_ms_roofline": pe.est_s * 1e3,
        "block": ([pe.block_m, pe.block_n]
                  if pe.block_m and pe.block_n else None),
        "value_dtype": pe.value_dtype,
    }
    # Zero-silent-fallback invariant input: would this tuned plan actually
    # run its planned kernel at dispatch, or fall back?  null == runs.
    rec["fallback"] = resolve_plan_fallback(g, pe)
    return rec


def bench_network(name: str, net: Sequence, *, image: int, batch: int,
                  iters: int = 2, wall: bool = True) -> Dict[str, Any]:
    """Roofline schedule rows (full geometry) + reduced-scale wall times."""
    rng = np.random.default_rng(0)
    doc: Dict[str, Any] = {"image": image, "batch": batch, "layers": []}
    # analytic rows at the paper's full geometry
    for layer, (c, h, w) in cnn.conv_layer_shapes(net, 3, ROOFLINE_IMAGE):
        if layer.sparsity == 0:
            continue
        rec = layer_record(layer_geometry(layer, c, h, w, batch))
        if rec is not None:
            doc["layers"].append(rec)
    if not wall:
        return doc
    # wall-clock for the record, at the reduced CPU scale
    params = cnn.init_cnn(net, 3, rng, image)
    shapes = cnn.conv_layer_shapes(net, 3, image)
    by_name = {r["name"]: r for r in doc["layers"]}
    for layer, (c, h, w) in shapes:
        if layer.sparsity == 0 or layer.name not in by_name:
            continue
        x = jnp.asarray(rng.standard_normal((batch, c, h, w)).astype(np.float32))
        fn = jax.jit(lambda x, e=params[layer.name]["ell"]: direct_sparse_conv(
            x, e, stride=layer.stride, padding=layer.pad))
        t = time_fn(fn, x, warmup=1, iters=iters)
        # time_fn returns TimingStats (value == p50); surface the spread so
        # wall rows are readable as [min, p50, max], not a bare point.
        by_name[layer.name]["wall_ms_direct"] = t * 1e3
        by_name[layer.name]["wall_ms_direct_min"] = t.min * 1e3
        by_name[layer.name]["wall_ms_direct_max"] = t.max * 1e3
    x = jnp.asarray(rng.standard_normal((batch, 3, image, image)).astype(np.float32))
    e2e, spread = {}, {}
    for method in ("dense", "csr-direct"):
        fn = lambda x, m=method: cnn.cnn_forward(net, params, x, method=m)  # noqa: E731
        t = time_fn(fn, x, warmup=1, iters=iters)
        e2e[method] = t * 1e3
        spread[method] = [t.min * 1e3, t.max * 1e3]
    doc["end_to_end_ms"] = e2e
    doc["end_to_end_ms_spread"] = spread
    return doc


def bench_robustness(name: str, *, requests: int = 30) -> Dict[str, Any]:
    """Serve one seeded heavy-traffic trace through the robust CNN serving
    tier (``repro.serving.robust``) twice — without faults and under the
    seeded chaos harness — on a reduced network slice and a virtual clock,
    so the with-vs-without latency distributions are deterministic.

    Both runs must terminate every request (``verify()``: zero lost, zero
    duplicated); the faulted run is expected to leave degradation evidence
    (ladder step-downs or build-time dropped rungs)."""
    from repro.engine import init_conv_params, lower
    from repro.serving import (BucketSpec, ChaosConfig, ChaosInjector,
                               RobustCnnServer, VirtualClock, arrival_trace,
                               slice_net)

    net = slice_net(name)
    params = init_conv_params(lower(net, (3, 12, 12)),
                              np.random.default_rng(0))

    def serve(chaos) -> Dict[str, Any]:
        server = RobustCnnServer(
            net, params, [BucketSpec(3, 12, 12, batch=2)],
            clock=VirtualClock(), queue_depth=16, max_attempts=6,
            cooldown_ticks=4, chaos=chaos)
        trace = arrival_trace(requests, [(3, 12, 12), (3, 10, 10)], seed=1,
                              mean_gap_s=0.0005, deadline_s=(1.0, 2.0))
        rep = server.run_trace(trace).verify()
        return {
            "p50_ms": rep.p50_latency_s * 1e3,
            "p99_ms": rep.p99_latency_s * 1e3,
            "completed": rep.completed, "rejected": rep.rejected,
            "retries": rep.retries, "lost": rep.lost,
            "straggler_ticks": rep.straggler_ticks,
            "degradation_events": len(rep.degradations),
            "dropped_rungs": len(rep.dropped_rungs),
            "rungs_executed": rep.rungs_executed,
        }

    chaos = ChaosInjector(ChaosConfig(
        seed=0, step_fault_rate=0.35, plan_corruption_rate=0.5,
        straggler_rate=0.1))
    return {"requests": requests, "chaos_seed": 0,
            "no_fault": serve(None), "with_faults": serve(chaos),
            "injected": chaos.summary()}


def check_robustness(doc: Dict[str, Any]) -> None:
    """Robustness acceptance bars: zero lost requests in both runs, a
    clean run that never degrades, and a faulted run that leaves
    degradation evidence."""
    for net, nd in doc["networks"].items():
        rb = nd.get("robustness")
        if rb is None:
            continue
        for run_name in ("no_fault", "with_faults"):
            if rb[run_name]["lost"]:
                raise AssertionError(
                    f"{net}/{run_name}: {rb[run_name]['lost']} request(s) "
                    f"lost under serving")
        clean = rb["no_fault"]
        if clean["degradation_events"] or clean["dropped_rungs"]:
            raise AssertionError(
                f"{net}/no_fault: degradation without injected faults "
                f"({clean['degradation_events']} events, "
                f"{clean['dropped_rungs']} dropped rungs)")
        faulted = rb["with_faults"]
        if not (faulted["degradation_events"] or faulted["dropped_rungs"]):
            raise AssertionError(
                f"{net}/with_faults: chaos left no degradation evidence")


def check_mxu_crossover(doc: Dict[str, Any]) -> None:
    """At least one moderate-sparsity layer (sparsity <= 0.75) must cross
    over to the bsr MXU path under roofline auto-selection — the BCSR conv
    path's acceptance bar."""
    for nd in doc["networks"].values():
        for rec in nd["layers"]:
            if (rec["sparsity"] <= 0.75
                    and rec.get("auto_roofline", {}).get("method") == "bsr"):
                return
    raise AssertionError(
        "no moderate-sparsity layer crossed over to the bsr MXU path")


def check_zero_fallback(doc: Dict[str, Any]) -> None:
    """No tuned layer may silently fall back: every layer's ``auto_roofline``
    plan must resolve a runnable schedule for its planned method (the
    zero-silent-fallback acceptance bar for the bench record)."""
    bad = [(net, rec["name"], rec["fallback"])
           for net, nd in doc["networks"].items()
           for rec in nd["layers"] if rec.get("fallback")]
    if bad:
        raise AssertionError(
            "tuned layers would silently fall back at dispatch: "
            + "; ".join(f"{n}/{l}: {r}" for n, l, r in bad))


def check_quantised_bytes(doc: Dict[str, Any]) -> None:
    """Every int8 schedule row must move *strictly* fewer HBM bytes than
    its f32 twin — the quantised value stream's acceptance bar.  (The
    weight traffic shrinks 4x minus one f32 scale per output channel; the
    inequality is strict at every real layer because the value block always
    outweighs the scale row.)  fp8 rows must never exceed their int8
    siblings (same itemsize, same scale row)."""
    for net, nd in doc["networks"].items():
        for rec in nd["layers"]:
            sch = rec["schedules"]
            for base in [k for k in sch if not k.endswith(("_int8", "_fp8"))]:
                q8 = sch.get(f"{base}_int8")
                if q8 is None:
                    raise AssertionError(
                        f"{net}/{rec['name']}: schedule {base!r} has no "
                        f"int8 twin row")
                if not q8["hbm_mb"] < sch[base]["hbm_mb"]:
                    raise AssertionError(
                        f"{net}/{rec['name']}/{base}: int8 row moves "
                        f"{q8['hbm_mb']} MiB, not strictly below the f32 "
                        f"twin's {sch[base]['hbm_mb']} MiB")
                fp8 = sch.get(f"{base}_fp8")
                if fp8 is not None and fp8["hbm_mb"] > q8["hbm_mb"]:
                    raise AssertionError(
                        f"{net}/{rec['name']}/{base}: fp8 row moves "
                        f"{fp8['hbm_mb']} MiB, above the int8 twin's "
                        f"{q8['hbm_mb']} MiB")


def check_stall_invariant(doc: Dict[str, Any]) -> None:
    """Pipelined staged-input stalls must be strictly fewer than blocking
    ones (equal only when both are already zero)."""
    for net, nd in doc["networks"].items():
        for rec in nd["layers"]:
            sch = rec["schedules"]
            if "pipelined" not in sch:
                continue
            blk = sch["blocking"]["staged_stall_ms"]
            pip = sch["pipelined"]["staged_stall_ms"]
            if not (pip < blk or (pip == blk == 0.0)):
                raise AssertionError(
                    f"{net}/{rec['name']}: pipelined stall {pip} not below "
                    f"blocking stall {blk}")


def run(out_path: Optional[str] = None, *, networks: Optional[Sequence[str]] = None,
        iters: int = 2, wall: bool = True) -> List[str]:
    path = pathlib.Path(out_path) if out_path else DEFAULT_PATH
    doc: Dict[str, Any] = {"schema": 1, "networks": {}}
    for name in (networks or SCALES):
        image, batch = SCALES[name]
        doc["networks"][name] = bench_network(
            name, cnn.NETWORKS[name](), image=image, batch=batch,
            iters=iters, wall=wall)
        doc["networks"][name]["robustness"] = bench_robustness(name)
    check_stall_invariant(doc)
    check_mxu_crossover(doc)
    check_zero_fallback(doc)
    check_quantised_bytes(doc)
    check_robustness(doc)
    path.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    out = []
    for name, nd in doc["networks"].items():
        blk = sum(r["schedules"]["blocking"]["staged_stall_ms"]
                  for r in nd["layers"])
        pip = sum(r["schedules"].get("pipelined",
                                     r["schedules"]["blocking"])
                  ["staged_stall_ms"] for r in nd["layers"])
        t_blk = sum(r["schedules"]["blocking"]["kernel_ms_roofline"]
                    for r in nd["layers"])
        t_pip = sum(r["schedules"].get("pipelined",
                                       r["schedules"]["blocking"])
                    ["kernel_ms_roofline"] for r in nd["layers"])
        out.append(row(
            f"bench/{name}/staging", t_pip * 1e-3,
            f"layers={len(nd['layers'])};"
            f"roofline_ms_blocking={t_blk:.3f};"
            f"roofline_ms_pipelined={t_pip:.3f};"
            f"stall_ms_blocking={blk:.3f};"
            f"stall_ms_pipelined={pip:.3f};json={path.name}"))
        t_bsr = sum(r["schedules"]["bsr"]["kernel_ms_roofline"]
                    for r in nd["layers"] if "bsr" in r["schedules"])
        n_bsr = sum(1 for r in nd["layers"] if "bsr" in r["schedules"])
        n_auto = sum(1 for r in nd["layers"]
                     if r.get("auto_roofline", {}).get("method") == "bsr")
        out.append(row(
            f"bench/{name}/bsr", t_bsr * 1e-3,
            f"layers_with_bsr={n_bsr};roofline_ms_bsr={t_bsr:.3f};"
            f"auto_picks_bsr={n_auto};json={path.name}"))
        mb_f32 = sum(r["schedules"]["blocking"]["hbm_mb"]
                     for r in nd["layers"])
        mb_i8 = sum(r["schedules"]["blocking_int8"]["hbm_mb"]
                    for r in nd["layers"])
        t_i8 = sum(r["schedules"]["blocking_int8"]["kernel_ms_roofline"]
                   for r in nd["layers"])
        n_q = sum(1 for r in nd["layers"]
                  if r.get("auto_roofline", {}).get("value_dtype",
                                                    "float32") != "float32")
        out.append(row(
            f"bench/{name}/quantised", t_i8 * 1e-3,
            f"hbm_mb_f32={mb_f32:.3f};hbm_mb_int8={mb_i8:.3f};"
            f"roofline_ms_int8={t_i8:.3f};auto_picks_quantised={n_q};"
            f"json={path.name}"))
        rb = nd["robustness"]
        out.append(row(
            f"bench/{name}/robustness", rb["with_faults"]["p99_ms"] * 1e-3,
            f"p99_ms_clean={rb['no_fault']['p99_ms']:.3f};"
            f"p99_ms_faulted={rb['with_faults']['p99_ms']:.3f};"
            f"retries={rb['with_faults']['retries']};"
            f"degradations={rb['with_faults']['degradation_events']};"
            f"dropped_rungs={rb['with_faults']['dropped_rungs']};"
            f"lost={rb['with_faults']['lost']};json={path.name}"))
    return out
