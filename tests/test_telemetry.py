"""Telemetry subsystem tests: metrics registry, Chrome-trace exporter,
one-time fallback warnings, plan-cache provenance counters, and the
engine's per-forward ExecutionReport (all three paper networks)."""
import json
import warnings

import numpy as np
import pytest

from repro import telemetry
from repro.engine import CnnEngine, lower
from repro.models import cnn
from repro.tuning import PlanCache, apply_plan_to_params, plan_program
from repro.tuning.measure import TimingStats, time_fn

SMOKE = [("alexnet", 67), ("googlenet", 48), ("resnet50", 48)]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Telemetry is process-global: every test starts and ends clean."""
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _micro_net():
    return [
        cnn.Conv("c0", 8, 3, 1, 1, sparsity=0.0), cnn.Relu(),
        cnn.Conv("c1", 8, 3, 1, 1, sparsity=0.75), cnn.Relu(),
        cnn.Pool("gap"), cnn.FC("fc", 10),
    ]


def _micro_engine(image=8):
    rng = np.random.default_rng(0)
    net = _micro_net()
    program = lower(net, (3, image, image))
    params = cnn.init_cnn(net, 3, rng, image)
    plan = plan_program(program, batch=1, mode="roofline", cache=PlanCache(),
                        backend="cpu")
    apply_plan_to_params(params, plan)
    x = rng.standard_normal((1, 3, image, image)).astype(np.float32)
    return CnnEngine(program, params, plan), x


# ---------------------------------------------------------------- metrics

def test_counter_and_gauge():
    c = telemetry.counter("t.c")
    c.inc()
    c.inc(3)
    telemetry.gauge("t.g").set(7)
    snap = telemetry.snapshot()
    assert snap["t.c"] == {"type": "counter", "value": 4}
    assert snap["t.g"]["value"] == 7.0


def test_histogram_quantiles():
    h = telemetry.histogram("t.h")
    for v in range(1, 101):
        h.observe(float(v))
    assert h.count == 100 and h.min == 1.0 and h.max == 100.0
    assert h.p50 == 50.0 and h.p95 == 95.0 and h.p99 == 99.0
    assert h.p50 <= h.p95 <= h.p99
    d = h.to_dict()
    assert d["mean"] == pytest.approx(50.5)
    # empty histogram quantiles are 0, not NaN/inf
    assert telemetry.histogram("t.empty").p99 == 0.0


def test_registry_type_mismatch_raises():
    telemetry.counter("t.typed")
    with pytest.raises(TypeError):
        telemetry.gauge("t.typed")


def test_reset_clears_registry():
    telemetry.counter("t.c").inc()
    telemetry.reset()
    assert telemetry.snapshot() == {}


# ----------------------------------------------------------------- tracer

def test_tracer_exports_valid_chrome_trace(tmp_path):
    tracer = telemetry.get_tracer()
    with telemetry.enabled(), telemetry.span("outer", foo=1):
        tracer.instant("marker", cat="test")
    tracer.complete("op", start_s=None, dur_s=1e-3, cat="test",
                    args={"method": "pallas"})
    doc = tracer.to_chrome_trace()
    telemetry.validate_chrome_trace(doc)  # must not raise
    assert doc["displayTimeUnit"] == "ms"
    phases = [ev["ph"] for ev in doc["traceEvents"]]
    assert "M" in phases and "X" in phases and "i" in phases
    path = tmp_path / "trace.json"
    tracer.export(str(path))
    telemetry.validate_chrome_trace(json.loads(path.read_text()))


def test_validate_chrome_trace_rejects_bad_docs():
    with pytest.raises(ValueError):
        telemetry.validate_chrome_trace({"no": "traceEvents"})
    with pytest.raises(ValueError):  # X event needs a non-negative dur
        telemetry.validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0, "pid": 1, "tid": 0,
             "dur": -5}]})
    with pytest.raises(ValueError):  # args must be JSON-serializable
        telemetry.validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "ts": 0, "dur": 1, "pid": 1, "tid": 0,
             "args": {"bad": object()}}]})


# ------------------------------------------------------------------ spans

def test_span_records_only_when_enabled():
    with telemetry.span("t.span", k=1):
        pass
    assert telemetry.snapshot() == {} and len(telemetry.get_tracer()) == 0

    with telemetry.enabled():
        with telemetry.span("t.span", k=1):
            pass
        with pytest.raises(ValueError), telemetry.span("t.span", k=2):
            raise ValueError("the span still closes")
    h = telemetry.histogram("t.span_s")
    assert h.count == 2 and h.min >= 0.0
    events = telemetry.get_tracer().events
    assert [(ev["name"], ev["ph"], ev["tid"], ev["args"]) for ev in events] \
        == [("t.span", "X", telemetry.TID_WALL, {"k": 1}),
            ("t.span", "X", telemetry.TID_WALL, {"k": 2})]


def test_span_annotation_on_profiler_host_plane(tmp_path):
    """The span's profiler annotation is on the host plane of the same
    trace as the device's operations, telemetry on or off."""
    import glob

    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("t.annotated"):
            jax.block_until_ready(jnp.arange(8.0) * 2)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    host = [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert host.count("t.annotated") == 1
    assert telemetry.snapshot() == {} and len(telemetry.get_tracer()) == 0


# --------------------------------------------------- fallback warnings

def test_fallback_warns_once_per_layer_and_reason():
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        telemetry.record_fallback("sparse_conv", "no_feasible_tiling",
                                  layer="conv2", geometry="m=4 c=4",
                                  fallback_to="csr-direct")
        telemetry.record_fallback("sparse_conv", "no_feasible_tiling",
                                  layer="conv2", geometry="m=4 c=4",
                                  fallback_to="csr-direct")
    hits = [x for x in w if issubclass(x.category,
                                       telemetry.SparseFallbackWarning)]
    assert len(hits) == 1  # once per (kernel, layer, reason), not per call
    msg = str(hits[0].message)
    assert "no_feasible_tiling" in msg and "conv2" in msg and "m=4" in msg

    # a different layer (and a different reason) each warn again
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        telemetry.record_fallback("sparse_conv", "no_feasible_tiling",
                                  layer="conv3")
        telemetry.record_fallback("sparse_conv", "smem_infeasible",
                                  layer="conv2")
    assert len(w) == 2


def test_fallback_warning_is_independent_of_telemetry_state():
    """The one-time warning fires with telemetry disabled (always-on);
    the counters only move when telemetry is enabled."""
    assert not telemetry.is_enabled()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        telemetry.record_fallback("bsr_conv", "smem_infeasible",
                                  layer="conv9", fallback_to="dense")
    assert len(w) == 1
    assert "fallback.total" not in telemetry.snapshot()

    telemetry.reset_warnings()
    with telemetry.enabled(), warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        telemetry.record_fallback("bsr_conv", "smem_infeasible",
                                  layer="conv9", fallback_to="dense")
    snap = telemetry.snapshot()
    assert snap["fallback.total"]["value"] == 1
    assert snap["fallback.bsr_conv.smem_infeasible"]["value"] == 1


def test_fallback_unknown_reason_raises():
    with pytest.raises(ValueError):
        telemetry.record_fallback("sparse_conv", "not_a_reason")


# ------------------------------------------------------------ TimingStats

def test_time_fn_returns_spread():
    t = time_fn(lambda: sum(range(200)), warmup=1, iters=5)
    assert isinstance(t, TimingStats) and isinstance(t, float)
    assert t.min <= t.p50 <= t.max
    assert t.p50 == float(t)
    assert t * 1e3 == pytest.approx(float(t) * 1e3)  # arithmetic still works
    assert t.spread == pytest.approx(t.max - t.min)


# ------------------------------------- plan-cache provenance counters

def test_plan_cache_migration_counters(tmp_path):
    """Loading every migratable schema (v1-v5) under telemetry counts each
    entry as a migration and marks its provenance; a current-version reload
    counts as cache hits instead."""
    from repro.tuning.cache import MIGRATABLE_VERSIONS

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
    assert set(fixtures) == set(MIGRATABLE_VERSIONS)
    with telemetry.enabled():
        for ver, entry in fixtures.items():
            p = tmp_path / f"v{ver}.json"
            p.write_text(json.dumps(
                {"version": ver, "entries": {"k": entry}}))
            cache = PlanCache(str(p))
            assert cache.get("k").provenance == "migrated"
        snap = telemetry.snapshot()
        assert snap["tuning.cache.loads"]["value"] == len(fixtures)
        assert snap["tuning.cache.load_migrations"]["value"] == len(fixtures)
        # re-persist one and reload: current version -> cache_hit, and the
        # migration counter does not move
        out = tmp_path / "v6.json"
        cache.save(str(out))
        assert PlanCache(str(out)).get("k").provenance == "cache_hit"
        snap = telemetry.snapshot()
        assert snap["tuning.cache.load_migrations"]["value"] == len(fixtures)
        assert snap["tuning.cache.loads"]["value"] == len(fixtures) + 1


def test_plan_provenance_fresh_then_cache_hit(tmp_path):
    """A fresh tune marks entries freshly_tuned (dense-kept layers:
    default); re-planning from the persisted cache marks them cache_hit and
    bumps the hit counter."""
    net = _micro_net()
    program = lower(net, (3, 8, 8))
    path = tmp_path / "cache.json"
    cache = PlanCache(str(path))
    plan = plan_program(program, batch=1, mode="roofline", cache=cache,
                        backend="cpu")
    assert all(pe.provenance in ("freshly_tuned", "default")
               for pe in plan.values())
    assert any(pe.provenance == "freshly_tuned" for pe in plan.values())

    with telemetry.enabled():
        replan = plan_program(program, batch=1, mode="roofline",
                              cache=PlanCache(str(path)), backend="cpu")
        assert replan == plan  # provenance is excluded from equality
        assert all(pe.provenance == "cache_hit" for pe in replan.values())
        assert (telemetry.snapshot()["tuning.plan.cache_hit"]["value"]
                == len(replan))


# -------------------------------------------------- ExecutionReport

@pytest.mark.parametrize("net_name,image", SMOKE)
def test_execution_report_all_networks(net_name, image):
    """Under a healthy tuned plan, every conv layer's report pins the
    planned method with zero silent fallbacks — built without executing."""
    rng = np.random.default_rng(0)
    net = cnn.NETWORKS[net_name]()
    program = lower(net, (3, image, image))
    params = cnn.init_cnn(net, 3, rng, image)
    plan = plan_program(program, batch=1, mode="roofline", cache=PlanCache(),
                        backend="cpu")
    apply_plan_to_params(params, plan)
    engine = CnnEngine(program, params, plan)

    report = engine.execution_report((1, 3, image, image), "auto")
    n_convs = len(program.conv_table)
    assert len(report.ops) == n_convs and n_convs > 0
    assert report.fallback_count == 0, report.format()
    for op in report.ops:
        assert op.method_executed == op.method_planned
        assert op.fallback_reason is None
        assert op.provenance in ("freshly_tuned", "default")
        assert op.flops > 0 and op.hbm_bytes > 0 and op.est_s > 0
    # the report names real executed methods, and the sparse layers left
    # the dense path
    assert set(report.methods_executed) <= {
        "dense", "lowered", "csr-direct", "pallas", "bsr"}
    sparse_ops = [o for o in report.ops if o.sparsity > 0]
    assert sparse_ops and all(o.method_executed != "dense"
                              for o in sparse_ops)
    # the rendered table carries one row per conv
    assert report.format().count("\n") >= n_convs
    # a forward records the measured engine.dispatch span, which exports
    # as a valid Chrome trace, and leaves the same report behind
    x = np.zeros((1, 3, image, image), np.float32)
    with telemetry.enabled():
        engine(x, "auto")
    doc = telemetry.get_tracer().to_chrome_trace()
    telemetry.validate_chrome_trace(doc)
    spans = [ev for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert [ev["name"] for ev in spans] == ["engine.dispatch"]
    assert telemetry.histogram("engine.dispatch_s").count == 1
    assert ([o.to_dict() for o in engine.last_report.ops]
            == [o.to_dict() for o in report.ops])


def test_forward_records_report_and_valid_trace(tmp_path):
    engine, x = _micro_engine()
    y_off = np.asarray(engine(x, "auto"))  # telemetry disabled
    assert engine.last_report is None
    assert telemetry.snapshot() == {} and len(telemetry.get_tracer()) == 0

    with telemetry.enabled():
        y_on = np.asarray(engine(x, "auto"))
    np.testing.assert_array_equal(y_off, y_on)  # bit-identical either way

    report = engine.last_report
    assert report is not None and not report.timed
    assert report.fallback_count == 0
    assert report.jit_cache_hit  # second forward reuses the compiled fn
    snap = telemetry.snapshot()
    assert snap["engine.forwards"]["value"] == 1
    assert snap["engine.jit_hits"]["value"] == 1
    # the measured dispatch span landed on the tracer and export validates
    assert snap["engine.dispatch_s"]["count"] == 1
    path = tmp_path / "trace.json"
    telemetry.get_tracer().export(str(path))
    doc = json.loads(path.read_text())
    telemetry.validate_chrome_trace(doc)
    names = [ev["name"] for ev in doc["traceEvents"] if ev["ph"] == "X"]
    assert names == ["engine.dispatch"]


def test_forward_timed_fills_wall_times():
    engine, x = _micro_engine()
    y = np.asarray(engine.forward_timed(x, "auto"))
    y_ref = np.asarray(engine(x, "auto"))
    np.testing.assert_allclose(y, y_ref, rtol=1e-5, atol=1e-6)
    report = engine.last_report
    assert report is not None and report.timed
    for op in report.ops:
        assert op.wall_s is not None and op.wall_s >= 0.0
    # timed mode records wall spans regardless of the global flag — calling
    # it is the opt-in
    assert len(telemetry.get_tracer()) > 0
    assert all(ev["name"].startswith("engine.timed.")
               for ev in telemetry.get_tracer().events)
    assert telemetry.histogram("engine.timed.c1_s").count == 1


def test_report_built_once_per_compiled_function(monkeypatch):
    """Telemetry-on forwards reuse one report per compiled function and
    rung: N forwards build each conv's OpReport once, and only
    ``jit_cache_hit`` changes from call to call."""
    engine, x = _micro_engine()
    built = []
    op_report = CnnEngine._op_report

    def counting(self, op, *args, **kw):
        built.append(op.name)
        return op_report(self, op, *args, **kw)

    monkeypatch.setattr(CnnEngine, "_op_report", counting)
    convs = sorted(op.name for op in engine.program.conv_ops)
    with telemetry.enabled():
        hits = []
        for _ in range(4):
            engine(x, "auto")
            hits.append(engine.last_report.jit_cache_hit)
        assert hits == [False, True, True, True]
        assert sorted(built) == convs
        engine(x, "auto", rung="tuned")  # another rung: another report
        assert engine.last_report.rung == "tuned"
        assert engine.last_report.jit_cache_hit
        assert sorted(built) == sorted(convs * 2)
    snap = telemetry.snapshot()
    assert snap["engine.forwards"]["value"] == 5
    assert snap["engine.jit_misses"]["value"] == 1
    assert snap["engine.dispatch_s"]["count"] == 5


def test_report_kept_tiles_match_dense_scan():
    """A bsr layer is priced at its bank's mean kept tiles per block-row,
    read from ``nblocks``: the count ``bcsr_true_kept`` scans the dense
    weights for, at every block shape."""
    import jax.numpy as jnp

    from repro.tuning.measure import bcsr_true_kept, candidate_cost
    from repro.tuning.planner import geometry_of_op
    from repro.tuning.space import Candidate

    rng = np.random.default_rng(1)
    net = [cnn.Conv("c0", 32, 3, 1, 1, sparsity=0.0), cnn.Relu(),
           cnn.Conv("c1", 32, 3, 1, 1, sparsity=0.5), cnn.Relu(),
           cnn.Pool("gap"), cnn.FC("fc", 10)]
    program = lower(net, (3, 8, 8))
    params = cnn.init_cnn(net, 3, rng, 8)
    w = np.asarray(params["c1"]["w"]).reshape(32, -1).copy()
    w[:8, 128:] = 0.0    # block-row 0 of (8, 128) keeps one tile of three
    w[8:16, :] = 0.0     # block-row 1 keeps none
    params["c1"]["w"] = jnp.asarray(w.reshape(32, 32, 3, 3))
    engine = CnnEngine(program, params)
    (op,) = [o for o in program.conv_ops if o.name == "c1"]
    dense = np.asarray(params["c1"]["w"])
    for block in ((8, 128), (16, 128), (8, 256)):
        bcc = engine._bcsr_for(op, params["c1"], block)
        assert engine._kept_tiles(op, bcc) == bcsr_true_kept(dense, *block)
    report = engine.execution_report((2, 3, 8, 8), "bsr")
    (o,) = [o for o in report.ops if o.name == "c1"]
    assert o.method_executed == "bsr"
    cand = Candidate(method="bsr", te=o.tiling["te"], fuse=True,
                     block_m=o.tiling["block_m"], block_n=o.tiling["block_n"])
    want = candidate_cost(geometry_of_op(op, batch=2, dtype="float32"), cand,
                          w_dense=dense)
    assert (o.flops, o.hbm_bytes, o.est_s) == (
        want["flops"], want["hbm_bytes"], want["est_s"])


def test_stale_bsr_plan_reports_fallback():
    """A stale bsr plan entry (no block shape) must surface as a
    machine-readable stale_plan_no_block fallback in the report."""
    import dataclasses

    engine, x = _micro_engine()
    stale = {k: pe for k, pe in engine.plan.items()}
    sparse_key = next(k for k, pe in stale.items()
                      if pe.method not in ("dense",))
    stale[sparse_key] = dataclasses.replace(
        stale[sparse_key], method="bsr", block_m=None, block_n=None)
    engine2 = CnnEngine(engine.program, engine.params, stale)
    report = engine2.execution_report(tuple(x.shape), "auto")
    bad = [o for o in report.ops if o.fallback_reason is not None]
    assert len(bad) == 1
    assert bad[0].fallback_reason == "stale_plan_no_block"
    assert bad[0].method_executed == "dense"
    assert report.fallback_count == 1


@pytest.mark.parametrize("net_name,n_pallas", [("resnet50", 18),
                                               ("googlenet", 31)])
def test_report_names_pointwise_schedule(net_name, n_pallas):
    """At 224 px, batch 32, the roofline plan's ELL layers are all 1x1
    stride-1 convs: each names the pointwise schedule in its report, none
    falls back, and tracing the forward counts each once in
    ``kernels.sparse_conv.pointwise``."""
    import jax
    import jax.numpy as jnp

    net = cnn.NETWORKS[net_name]()
    engine = CnnEngine(lower(net, (3, 224, 224)),
                       cnn.init_cnn(net, 3, np.random.default_rng(0), 224))
    x = jax.ShapeDtypeStruct((32, 3, 224, 224), jnp.float32)
    report = engine.execution_report(x, "auto")
    assert report.fallback_count == 0, report.format()
    pallas = [o for o in report.ops if o.method_executed == "pallas"]
    assert len(pallas) == n_pallas
    for o in pallas:
        assert o.tiling["schedule"] == "pointwise" and o.tiling["tp"] >= 8
    with telemetry.enabled():
        jax.eval_shape(lambda x: engine(x, "auto"), x)
    snap = telemetry.snapshot()
    assert snap["kernels.sparse_conv.pointwise"]["value"] == n_pallas
    assert "fallback.total" not in snap
