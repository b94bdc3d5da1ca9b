"""Render the §Dry-run summary (compile proof + memory) for EXPERIMENTS.md,
and (``--smoke``) a CI-sized regression check of the benchmark tables.

The smoke mode exists so benchmark-table regressions — import errors in a
figure module, renamed rows, a method column silently dropped — fail in CI
instead of at paper-figure time: it imports every suite ``benchmarks.run``
dispatches to, then runs the fig11 end-to-end table on a micro network
(interpret-mode Pallas included) and checks the expected row names.

  PYTHONPATH=src python -m benchmarks.dryrun_summary            # table
  PYTHONPATH=src python -m benchmarks.dryrun_summary --smoke    # CI check
"""
from __future__ import annotations

import argparse
import json
import pathlib

RESULTS = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def render() -> None:
    rows = []
    for p in sorted(RESULTS.glob("*.json")):
        parts = p.stem.split("__")
        if len(parts) != 3:
            continue  # tagged hillclimb runs are listed in §Perf instead
        d = json.loads(p.read_text())
        rows.append(d)
    print(f"{len(rows)} cells compiled\n")
    print("| arch | shape | mesh | compile (s) | args/dev (GB) | temp/dev (GB) "
          "| coll/dev (GB, raw scan) | probes |")
    print("|---|---|---|---|---|---|---|---|")
    for d in rows:
        print(f"| {d['arch']} | {d['shape']} | {d['mesh']} "
              f"| {d.get('compile_s', 0):.0f} "
              f"| {d.get('mem_arg_bytes', 0)/2**30:.2f} "
              f"| {d.get('mem_temp_bytes', 0)/2**30:.2f} "
              f"| {sum(json.loads(json.dumps(d.get('coll_breakdown', {}))).values())/2**30:.2f} "
              f"| {'y' if d.get('probe_info') else '-'} |")


def smoke() -> None:
    """Import every benchmark suite and spot-check the fig11 table rows, the
    BENCH_sparse_conv.json schedule rows (pipeline axis + the bsr MXU
    crossover + the zero-silent-fallback invariant), the plan-cache v1→v5
    migrations, and one telemetry-traced engine forward (valid Chrome-trace
    JSON, per-op ExecutionReport, zero fallbacks)."""
    # Import errors in any figure module fail here, like benchmarks.run would.
    from benchmarks import (bench_sparse_conv, fig8_sparse_conv,  # noqa: F401
                            fig9_breakdown, fig10_locality, fig11_end2end,
                            fig12_autotune, kernels, roofline_table, run)
    from repro.models import cnn

    micro = [
        cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
        cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu(),
        cnn.Pool("gap"), cnn.FC("fc", 10),
    ]
    rows = fig11_end2end.bench_network("micro", micro, image=8, batch=1,
                                       iters=1, pallas_iters=1)
    names = {r.split(",")[0] for r in rows}
    expect = {f"fig11/micro/{m}" for m in fig11_end2end.METHOD_ROWS}
    missing = expect - names
    if missing:
        raise SystemExit(f"benchmark smoke: missing fig11 rows {sorted(missing)}")
    for r in rows:
        print(r)
    _smoke_bench_json(bench_sparse_conv)
    _smoke_cache_migrations()
    _smoke_traced_forward()
    _smoke_quantised_forward()
    _smoke_chaos_forward()
    _smoke_static_verifier()
    print(f"benchmark smoke ok: {len(names)} fig11 rows, all suites import, "
          "bench json pipeline + bsr + quantised rows + zero fallbacks, "
          "cache v1-v5 -> v6 migrations, traced + int8-pinned forwards "
          "valid, chaos serving zero-lost + degradation recorded, "
          "static verifier clean")


def _smoke_bench_json(bench_sparse_conv) -> None:
    """BENCH_sparse_conv.json must carry both halo-DMA schedule rows plus a
    bsr (MXU) row, the pipelined staged-input stalls must be strictly fewer,
    and at least one moderate-sparsity layer must cross over to the bsr
    path under roofline auto-selection."""
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        path = pathlib.Path(td) / "bench.json"
        bench_sparse_conv.run(str(path), networks=["alexnet"], wall=False)
        doc = json.loads(path.read_text())
        layers = doc["networks"]["alexnet"]["layers"]
        if not layers:
            raise SystemExit("bench smoke: no sparse-conv layer records")
        for rec in layers:
            sch = rec["schedules"]
            if "blocking" not in sch or "pipelined" not in sch:
                raise SystemExit(
                    f"bench smoke: {rec['name']} missing a schedule row")
            if "auto_roofline" not in rec:
                raise SystemExit(
                    f"bench smoke: {rec['name']} missing the auto row")
        if not any("bsr" in rec["schedules"] for rec in layers):
            raise SystemExit("bench smoke: no bsr (MXU) schedule rows")
        for rec in layers:
            if "blocking_int8" not in rec["schedules"]:
                raise SystemExit(
                    f"bench smoke: {rec['name']} missing the int8 twin row")
            if "value_dtype" not in rec.get("auto_roofline", {}):
                raise SystemExit(
                    f"bench smoke: {rec['name']} auto row missing "
                    f"value_dtype")
        # the invariants already ran inside run(); assert they are wired
        bench_sparse_conv.check_stall_invariant(doc)
        bench_sparse_conv.check_mxu_crossover(doc)
        bench_sparse_conv.check_zero_fallback(doc)
        bench_sparse_conv.check_quantised_bytes(doc)
        # every record must carry the fallback field (null == plan runs)
        for rec in layers:
            if "fallback" not in rec:
                raise SystemExit(
                    f"bench smoke: {rec['name']} missing the fallback field")


def _smoke_cache_migrations() -> None:
    """Every migratable plan-cache schema (v1-v5) loads, defaults the fields
    its kernels predate, and re-persists as the current version."""
    import tempfile

    from repro.tuning.cache import CACHE_VERSION, MIGRATABLE_VERSIONS, PlanCache

    fixtures = {
        1: {"method": "pallas", "tm": 64, "pad_to": 8},
        2: {"method": "pallas", "tm": 32, "te": 16, "tf": 16, "pad_to": 8},
        3: {"method": "pallas", "tm": 16, "te": 16, "tf": 16, "pad_to": 8,
            "fuse": True},
        4: {"method": "pallas", "tm": 16, "te": 16, "tf": 16, "pad_to": 8,
            "fuse": True, "pipeline": True, "permute": True},
        5: {"method": "bsr", "te": 16, "tf": 16, "fuse": True,
            "block_m": 8, "block_n": 128},
    }
    if set(fixtures) != set(MIGRATABLE_VERSIONS):
        raise SystemExit("cache smoke: fixture set out of date with "
                         f"MIGRATABLE_VERSIONS={MIGRATABLE_VERSIONS}")
    with tempfile.TemporaryDirectory() as td:
        for ver, entry in fixtures.items():
            p = pathlib.Path(td) / f"v{ver}.json"
            p.write_text(json.dumps({"version": ver, "entries": {"k": entry}}))
            cache = PlanCache(str(p))
            pe = cache.get("k")
            if ver < 4 and (pe.pipeline or pe.permute):
                raise SystemExit(
                    f"cache smoke: v{ver} entry migrated with a non-blocking "
                    "schedule")
            if ver < 5 and (pe.block_m is not None or pe.block_n is not None):
                raise SystemExit(
                    f"cache smoke: v{ver} entry migrated with a BCSR block "
                    "shape no pre-v5 kernel ran")
            if pe.value_dtype != "float32":
                raise SystemExit(
                    f"cache smoke: v{ver} entry migrated with a quantised "
                    "value stream no pre-v6 kernel ran")
            out = pathlib.Path(td) / f"v{ver}-migrated.json"
            cache.save(str(out))
            doc = json.loads(out.read_text())
            if doc["version"] != CACHE_VERSION:
                raise SystemExit(
                    f"cache smoke: v{ver} re-persisted as {doc['version']}, "
                    f"want {CACHE_VERSION}")


def _smoke_traced_forward() -> None:
    """One telemetry-enabled engine forward on a micro network must produce
    a per-op ExecutionReport with zero silent fallbacks and a Chrome-trace
    JSON that passes schema validation."""
    import tempfile

    import jax
    import numpy as np

    from repro import telemetry
    from repro.engine import CnnEngine, lower
    from repro.models import cnn
    from repro.tuning import PlanCache, apply_plan_to_params, plan_program

    micro = [
        cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
        cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu(),
        cnn.Pool("gap"), cnn.FC("fc", 10),
    ]
    rng = np.random.default_rng(0)
    program = lower(micro, (3, 8, 8))
    params = cnn.init_cnn(micro, 3, rng, 8)
    plan = plan_program(program, batch=1, mode="roofline", cache=PlanCache(),
                        backend=jax.devices()[0].platform)
    apply_plan_to_params(params, plan)
    engine = CnnEngine(program, params, plan)
    x = rng.standard_normal((1, 3, 8, 8)).astype(np.float32)

    telemetry.reset()
    with telemetry.enabled():
        engine(x, "auto")
        report = engine.last_report
        if report is None:
            raise SystemExit("trace smoke: no ExecutionReport recorded")
        if report.fallback_count:
            raise SystemExit(
                "trace smoke: traced forward took silent fallbacks: "
                f"{[(o.name, o.fallback_reason) for o in report.fallback_ops]}")
        conv_ops = [o for o in report.ops]
        if not conv_ops or any(not o.method_executed for o in conv_ops):
            raise SystemExit("trace smoke: report missing per-op methods")
        tracer = telemetry.get_tracer()
        if not any(ev["name"] == "engine.dispatch" for ev in tracer.events):
            raise SystemExit("trace smoke: no engine.dispatch span recorded")
        with tempfile.TemporaryDirectory() as td:
            path = pathlib.Path(td) / "trace.json"
            tracer.export(str(path))  # export() validates before writing
            doc = json.loads(path.read_text())
            telemetry.validate_chrome_trace(doc)
            if not any(ev.get("ph") == "X" for ev in doc["traceEvents"]):
                raise SystemExit("trace smoke: no complete (X) span events")
    telemetry.reset()


def _smoke_quantised_forward() -> None:
    """One engine forward with an int8-pinned plan (the CI bench-smoke leg
    for the quantised value streams): every sparse conv must execute its
    planned kernel on the int8 bank — no silent fallbacks — and the output
    must agree with the f32-bank forward to quantisation tolerance."""
    import dataclasses

    import jax
    import numpy as np

    from repro import telemetry
    from repro.engine import CnnEngine, lower
    from repro.models import cnn
    from repro.tuning import PlanCache, apply_plan_to_params, plan_program

    micro = [
        cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
        cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu(),
        cnn.Pool("gap"), cnn.FC("fc", 10),
    ]
    rng = np.random.default_rng(0)
    program = lower(micro, (3, 8, 8))
    params = cnn.init_cnn(micro, 3, rng, 8)
    plan = plan_program(program, batch=1, mode="roofline", cache=PlanCache(),
                        backend=jax.devices()[0].platform)
    plan = {name: (dataclasses.replace(pe, value_dtype="int8")
                   if pe.method in ("pallas", "bsr") else pe)
            for name, pe in plan.items()}
    if not any(pe.value_dtype == "int8" for pe in plan.values()):
        raise SystemExit("quantised smoke: no pallas/bsr entry to pin int8")
    qparams = apply_plan_to_params(params, plan)
    x = rng.standard_normal((1, 3, 8, 8)).astype(np.float32)
    engine = CnnEngine(program, qparams, plan, strict=True)
    telemetry.reset()
    with telemetry.enabled():
        y_q = np.asarray(engine(x, "auto"))
        report = engine.last_report
    telemetry.reset()
    if report is None or report.fallback_count:
        raise SystemExit(
            "quantised smoke: int8-pinned forward took silent fallbacks: "
            f"{[(o.name, o.fallback_reason) for o in report.fallback_ops]}")
    if not any(o.value_dtype == "int8" for o in report.ops):
        raise SystemExit(
            "quantised smoke: no op executed an int8 value stream")
    y_f = np.asarray(CnnEngine(program, params, None)(x, "dense"))
    denom = float(np.abs(y_f).max()) or 1.0
    rel = float(np.abs(y_q - y_f).max()) / denom
    if not np.isfinite(rel) or rel > 0.05:
        raise SystemExit(
            f"quantised smoke: int8 forward diverges from f32 (rel={rel})")


def _smoke_chaos_forward() -> None:
    """The fault-tolerant CNN serving tier must complete a seeded chaos
    trace with zero lost/duplicated requests and recorded degradation
    evidence, and a corrupted plan-cache file must degrade resiliently
    (``PlanCacheWarning``), never crash the server build."""
    import tempfile
    import warnings

    import jax
    import numpy as np

    from repro.engine import init_conv_params, lower
    from repro.serving import (BucketSpec, ChaosConfig, ChaosInjector,
                               RobustCnnServer, VirtualClock, arrival_trace,
                               corrupt_plan_cache_file, slice_net)
    from repro.tuning import PlanCache, plan_program
    from repro.tuning.cache import PlanCacheWarning

    net = slice_net("alexnet")
    program = lower(net, (3, 12, 12))
    params = init_conv_params(program, np.random.default_rng(0))
    with tempfile.TemporaryDirectory() as td:
        # Plan-cache corruption seam: persist a tuned cache, mangle it on
        # disk, and build the server against the corrupted file.
        cache_path = str(pathlib.Path(td) / "plans.json")
        cache = PlanCache(cache_path)
        plan_program(program, batch=2, mode="roofline", cache=cache,
                     params=params, backend=jax.devices()[0].platform)
        corrupt_plan_cache_file(cache_path, mode="garbage")
        chaos = ChaosInjector(ChaosConfig(
            seed=0, step_fault_rate=0.4, plan_corruption_rate=1.0,
            straggler_rate=0.1))
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            server = RobustCnnServer(
                net, params, [BucketSpec(3, 12, 12, batch=2)],
                plan_cache=cache_path, clock=VirtualClock(), queue_depth=16,
                max_attempts=6, chaos=chaos)
        if not any(issubclass(w.category, PlanCacheWarning) for w in caught):
            raise SystemExit(
                "chaos smoke: corrupted plan cache loaded without a "
                "PlanCacheWarning")
    trace = arrival_trace(20, [(3, 12, 12)], seed=1, mean_gap_s=0.0005,
                          deadline_s=(1.0, 2.0))
    rep = server.run_trace(trace)
    if rep.lost or rep.duplicated:
        raise SystemExit(
            f"chaos smoke: {rep.lost} lost / {rep.duplicated} duplicated "
            f"request(s) under injected faults")
    if not (rep.degradations or rep.dropped_rungs):
        raise SystemExit(
            "chaos smoke: chaos run recorded no degradation event")
    if not chaos.corrupted_entries:
        raise SystemExit("chaos smoke: plan corruption seam never fired")


def _smoke_static_verifier() -> None:
    """The pre-flight verifier must report zero errors over every network,
    its shipped default plan, and the kernel sources — the same gate CI's
    static-analysis job runs via `python -m repro.analysis check`."""
    from repro.analysis.checker import run_check

    report = run_check()
    if report.errors:
        raise SystemExit(
            "static-verifier smoke: "
            + "; ".join(d.format() for d in report.errors))
    if report.warnings:
        raise SystemExit(
            "static-verifier smoke: unexpected warnings: "
            + "; ".join(d.format() for d in report.warnings))
    if not report.checked:
        raise SystemExit("static-verifier smoke: nothing was checked")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI regression check of the benchmark tables")
    args = ap.parse_args()
    if args.smoke:
        smoke()
    else:
        render()


if __name__ == "__main__":
    main()
