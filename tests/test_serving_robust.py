"""Fault-tolerant CNN serving tier tests: admission control, the
degradation ladder, retry/backoff classification, and the seeded chaos
harness's zero-lost acceptance bar."""
import numpy as np
import pytest

from repro import telemetry
from repro.engine import init_conv_params, lower
from repro.runtime.fault_tolerance import Backoff
from repro.serving import (REJECT_REASONS, BucketSpec, ChaosConfig,
                           ChaosFatalError, ChaosInjector,
                           ChaosRetryableError, InferenceRequest,
                           RobustCnnServer, VirtualClock, arrival_trace,
                           corrupt_plan_cache_file, slice_net)

NETS = ("alexnet", "googlenet", "resnet50")


class ScriptedChaos:
    """Chaos stand-in with a scripted fault sequence: deterministic tests
    drive exact retry/escalate paths through the production machinery."""

    def __init__(self, faults=()):
        self.faults = list(faults)

    def draw_step_fault(self):
        return self.faults.pop(0) if self.faults else None

    def inflate_tick(self, dt):
        return dt, False

    def corrupt_plan(self, plan, program):
        return plan


@pytest.fixture(scope="module")
def alex():
    net = slice_net("alexnet")
    params = init_conv_params(lower(net, (3, 12, 12)),
                              np.random.default_rng(0))
    return net, params


def _server(alex, **kw):
    net, params = alex
    kw.setdefault("clock", VirtualClock())
    kw.setdefault("queue_depth", 8)
    buckets = kw.pop("buckets", [BucketSpec(3, 12, 12, batch=2)])
    return RobustCnnServer(net, params, buckets, **kw)


def _req(rid, shape=(3, 12, 12), **kw):
    return InferenceRequest(rid=rid, shape=shape, **kw)


# -- ladder construction ----------------------------------------------------

@pytest.mark.parametrize("name", NETS)
def test_ladder_builds_and_verifies_clean(name):
    net = slice_net(name)
    params = init_conv_params(lower(net, (3, 12, 12)),
                              np.random.default_rng(0))
    srv = RobustCnnServer(net, params, [BucketSpec(3, 12, 12, batch=2)],
                          clock=VirtualClock())
    (bucket,) = srv._buckets
    names = [r.name for r in bucket.rungs]
    assert names[0] == "tuned" and names[-1] == "dense"
    assert not srv.dropped_rungs
    for rung in bucket.rungs:
        # Every served rung passed the static gate: no silent fallbacks.
        assert rung.report.fallback_count == 0
        assert rung.report.rung == rung.name
        assert rung.est_s > 0


def test_quantised_rung_narrows_sparse_entries(alex):
    srv = _server(alex)
    (bucket,) = srv._buckets
    by_name = {r.name: r for r in bucket.rungs}
    if "quantised" in by_name:
        q = by_name["quantised"].plan
        assert any(pe.value_dtype == "int8" for pe in q.values()
                   if pe.method in ("pallas", "bsr"))
    dense = by_name["dense"].plan
    assert all(pe.method == "dense" for pe in dense.values())


def test_corrupted_plan_drops_rung_not_service(alex):
    """A chaos-corrupted (statically infeasible) tuned plan is caught by
    the build-time verifier: the rung is dropped, traffic runs the next
    rung down, nothing is lost."""
    chaos = ChaosInjector(ChaosConfig(seed=0, plan_corruption_rate=1.0))
    srv = _server(alex, chaos=chaos)
    (bucket,) = srv._buckets
    assert chaos.corrupted_entries
    assert srv.dropped_rungs
    assert all(d["preflight_errors"] or d["fallback_reasons"]
               for d in srv.dropped_rungs)
    assert "tuned" not in [r.name for r in bucket.rungs]
    rep = srv.run_trace(arrival_trace(6, [(3, 12, 12)], seed=1)).verify()
    assert rep.completed == 6


# -- admission control ------------------------------------------------------

def test_rejection_no_bucket(alex):
    srv = _server(alex)
    r = _req(0, shape=(1, 12, 12))  # channel count no bucket serves
    assert srv.submit(r) is False
    assert r.status == "rejected" and r.reject_reason == "no_bucket"


def test_rejection_queue_full(alex):
    srv = _server(alex, queue_depth=2)
    rs = [_req(i) for i in range(4)]
    admitted = [srv.submit(r) for r in rs]
    assert admitted == [True, True, False, False]
    assert rs[2].reject_reason == rs[3].reject_reason == "queue_full"
    assert all(r in REJECT_REASONS for r in ("queue_full", "no_bucket"))


def test_rejection_deadline_expired(alex):
    srv = _server(alex)
    r = _req(0, deadline_s=0.001)
    srv.submit(r)
    srv.clock.advance(1.0)  # deadline passes while queued
    srv.tick()
    assert r.status == "rejected" and r.reject_reason == "deadline_expired"


def test_smaller_shapes_pad_into_bucket(alex):
    srv = _server(alex)
    x = np.random.default_rng(0).standard_normal((3, 10, 10)).astype(
        np.float32)
    r = InferenceRequest(rid=0, x=x)
    srv.submit(r)
    srv.tick()
    assert r.status == "done" and r.result is not None
    assert r.bucket == "3x12x12b2"


def test_drain_exhausted_rejects_leftovers(alex):
    srv = _server(alex)
    trace = arrival_trace(10, [(3, 12, 12)], seed=0, mean_gap_s=0.0,
                          deadline_s=None)
    rep = srv.run_trace(trace, max_ticks=2).verify()  # budget too small
    assert rep.rejected.get("drain_exhausted", 0) > 0
    assert rep.lost == 0


# -- retry / failure classification -----------------------------------------

def test_retryable_fault_retries_then_completes(alex):
    srv = _server(alex, chaos=ScriptedChaos([
        ChaosRetryableError("UNAVAILABLE: injected (chaos)")]))
    r = _req(0)
    srv.submit(r)
    srv.tick()                      # faulted dispatch -> re-enqueued
    assert r.status == "queued" and r.attempts == 1
    assert r.not_before_s > srv.clock.now() - 1e-9
    srv.clock.advance(srv.backoff.delay_s(0))
    srv.tick()                      # backoff expired -> served
    assert r.status == "done"
    rep = srv.slo_report()
    assert rep.retries == 1 and rep.lost == 0


def test_retries_exhausted_rejects(alex):
    faults = [ChaosRetryableError("UNAVAILABLE: injected (chaos)")] * 5
    srv = _server(alex, chaos=ScriptedChaos(faults), max_attempts=2)
    r = _req(0)
    srv.submit(r)
    srv.tick()
    srv.clock.advance(10.0)
    srv.tick()
    assert r.status == "rejected" and r.reject_reason == "retries_exhausted"


def test_fatal_fault_rejects_immediately(alex):
    srv = _server(alex, chaos=ScriptedChaos([
        ChaosFatalError("injected device loss (chaos)")]))
    r = _req(0)
    srv.submit(r)
    srv.tick()
    assert r.status == "rejected" and r.reject_reason == "fatal_error"
    assert srv.slo_report().lost == 0


def test_backoff_policy_deterministic_and_capped():
    b = Backoff(base_s=0.1, mult=2.0, cap_s=0.5)
    assert [b.delay_s(i) for i in range(4)] == [0.1, 0.2, 0.4, 0.5]
    with pytest.raises(ValueError):
        Backoff(base_s=0.0)


# -- the degradation ladder at runtime --------------------------------------

def test_escalating_faults_step_down_then_recover(alex):
    """max_strikes consecutive retryable faults escalate: the bucket steps
    down a rung; a cool-down of healthy ticks steps it back up."""
    faults = [ChaosRetryableError("UNAVAILABLE: injected (chaos)")] * 3
    srv = _server(alex, chaos=ScriptedChaos(faults), max_strikes=3,
                  max_attempts=10, cooldown_ticks=2,
                  backoff=Backoff(base_s=0.001))
    (bucket,) = srv._buckets
    assert len(bucket.rungs) >= 2
    top = bucket.rungs[0].name
    r = _req(0)
    srv.submit(r)
    for _ in range(3):              # three strikes -> escalate
        srv.tick()
        srv.clock.advance(1.0)
    downs = [e for e in srv.events if e.reason == "escalate"]
    assert len(downs) == 1 and downs[0].from_rung == top
    assert bucket.rung_idx == 1
    # healthy ticks at the degraded rung recover the ladder
    srv.tick()                      # serves r at the degraded rung
    assert r.status == "done" and r.rung == bucket.rungs[1].name
    for i in range(3):
        r2 = _req(10 + i)
        srv.submit(r2)
        srv.tick()
    ups = [e for e in srv.events if e.reason == "recovered"]
    assert len(ups) == 1 and ups[0].to_rung == top
    assert bucket.rung_idx == 0


def test_overload_steps_down(alex):
    srv = _server(alex, queue_depth=4, high_water=0.5, cooldown_ticks=100)
    for i in range(4):
        srv.submit(_req(i))
    srv.tick()
    assert any(e.reason == "overload" for e in srv.events)


def test_rung_recorded_on_reports_and_requests(alex):
    srv = _server(alex)
    (bucket,) = srv._buckets
    r = _req(0)
    srv.submit(r)
    with telemetry.enabled():
        srv.tick()
        report = bucket.engine.last_report
    telemetry.reset()
    assert r.rung == bucket.rungs[0].name
    assert report.rung == r.rung
    assert report.to_dict()["rung"] == r.rung
    assert f"rung={r.rung}" in report.format()


# -- chaos acceptance -------------------------------------------------------

@pytest.mark.parametrize("name", NETS)
def test_heavy_chaos_trace_loses_nothing(name):
    """The acceptance bar: under seeded step faults, plan corruption, and
    stragglers, a heavy-traffic trace terminates every request exactly
    once, with machine-readable reasons on every rejection."""
    net = slice_net(name)
    params = init_conv_params(lower(net, (3, 12, 12)),
                              np.random.default_rng(0))
    chaos = ChaosInjector(ChaosConfig(
        seed=0, step_fault_rate=0.35, plan_corruption_rate=0.5,
        straggler_rate=0.2))
    srv = RobustCnnServer(net, params, [BucketSpec(3, 12, 12, batch=2)],
                          clock=VirtualClock(), queue_depth=16,
                          max_attempts=6, chaos=chaos)
    trace = arrival_trace(20, [(3, 12, 12), (3, 10, 10)], seed=2,
                          mean_gap_s=0.0005, deadline_s=(1.0, 2.0))
    rep = srv.run_trace(trace).verify()
    assert rep.submitted == 20
    assert rep.degradations or rep.dropped_rungs
    for r in srv.requests:
        assert r.status in ("done", "rejected")
        if r.status == "rejected":
            assert r.reject_reason in REJECT_REASONS
        else:
            assert r.rung is not None and r.result is not None


def test_chaos_replays_identically(alex):
    """Same seed, same workload -> identical SLO summary (the property the
    whole harness exists for)."""
    def run():
        srv = _server(alex, chaos=ChaosInjector(ChaosConfig(
            seed=5, step_fault_rate=0.4, straggler_rate=0.3)),
            max_attempts=6, queue_depth=16)
        trace = arrival_trace(15, [(3, 12, 12)], seed=3, mean_gap_s=0.001)
        return srv.run_trace(trace).verify().to_dict()

    assert run() == run()


def test_straggler_ticks_observed(alex):
    chaos = ChaosInjector(ChaosConfig(seed=1, straggler_rate=0.3,
                                      straggler_factor=50.0))
    srv = _server(alex, chaos=chaos, queue_depth=32)
    trace = arrival_trace(30, [(3, 12, 12)], seed=4, mean_gap_s=0.0,
                          deadline_s=None)
    rep = srv.run_trace(trace).verify()
    assert chaos.injected_stragglers > 0
    assert rep.straggler_ticks > 0


def test_telemetry_counters_namespaced(alex):
    telemetry.reset()
    with telemetry.enabled():
        srv = _server(alex, queue_depth=2)
        for i in range(4):
            srv.submit(_req(i, deadline_s=None))
        while srv.pending():
            srv.tick()
        snap = telemetry.snapshot()
    telemetry.reset()
    assert snap["serving.cnn.submitted"]["value"] == 4
    assert snap["serving.cnn.admitted"]["value"] == 2
    assert snap["serving.cnn.completed"]["value"] == 2
    assert snap["serving.cnn.rejected"]["value"] == 2
    assert snap["serving.cnn.rejected.queue_full"]["value"] == 2


def test_chaos_off_records_nothing(alex):
    telemetry.reset()
    srv = _server(alex)
    srv.submit(_req(0))
    srv.tick()
    assert telemetry.snapshot() == {}  # zero-overhead-when-off discipline
    assert len(telemetry.get_tracer()) == 0


def test_tick_records_stage_spans_once_per_batch(alex):
    """Each dispatched batch records its three stages: the padded batch
    copied to the device, the engine's dispatch, the fetch of the logits."""
    telemetry.reset()
    srv = _server(alex)
    with telemetry.enabled():
        for i in range(3):
            srv.submit(_req(i))
        batches = 0
        while srv.pending():
            batches += srv.tick() > 0
        snap = telemetry.snapshot()
        names = [ev["name"] for ev in telemetry.get_tracer().events]
    telemetry.reset()
    assert batches == 2
    for name in ("serving.cnn.batch_input", "engine.dispatch",
                 "serving.cnn.fetch"):
        assert snap[name + "_s"]["count"] == batches
    assert snap["serving.cnn.tick_latency_s"]["count"] == batches
    assert names == ["serving.cnn.batch_input", "engine.dispatch",
                     "serving.cnn.fetch"] * batches


# -- plan-cache corruption seam ---------------------------------------------

@pytest.mark.parametrize("mode", ("garbage", "truncate", "bad_entry"))
def test_corrupt_plan_cache_degrades_resiliently(tmp_path, mode, alex):
    from repro.tuning import PlanCache, plan_program
    from repro.tuning.cache import PlanCacheWarning

    net, params = alex
    program = lower(net, (3, 12, 12))
    path = str(tmp_path / "plans.json")
    plan_program(program, batch=2, mode="roofline", cache=PlanCache(path),
                 params=params, backend="cpu")
    corrupt_plan_cache_file(path, mode=mode)
    with pytest.warns(PlanCacheWarning):
        srv = RobustCnnServer(net, params, [BucketSpec(3, 12, 12, batch=2)],
                              plan_cache=path, clock=VirtualClock())
    rep = srv.run_trace(arrival_trace(4, [(3, 12, 12)], seed=0)).verify()
    assert rep.completed == 4
