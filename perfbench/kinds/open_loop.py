"""Open-loop serving (MLPerf Inference "Server": independent users send
single images on a Poisson schedule; tail latency is judged).

The traffic file gives the server's bucket batch and the size of the image
pool; the cell file gives the fixed offered rate.  Set-up makes the weights
and the images from the seed, builds ``RobustCnnServer`` with one bucket
at the configuration's input size (wall clock, no chaos, no deadlines,
server defaults otherwise), and compiles every rung of its degradation
ladder, so that no compile falls in the window even if the ladder steps
down.

The window is the schedule: the generator submits every request that is
due, calls ``tick()``, and sleeps only until the next due time when nothing
is queued.  Requests due in the window are served to completion after it,
for at most ``drain_s`` more seconds.  A request's latency runs from its
due time in the schedule to its logits on the host, so a request that
falls due while a forward blocks the loop is charged the wait.  A request
that is rejected or never completes counts as missing, at infinite
latency.  After the window, every completed request's logits are compared
with the reference's logits for its image.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

from perfbench import arrivals, correct, harness, reference, system, weights


def nearest_rank(values, q: float) -> float:
    xs = sorted(values)
    return xs[max(0, math.ceil(q * len(xs)) - 1)]


def warm_rungs(server) -> None:
    """One forward at every rung of every bucket's ladder, on a batch made
    by the server's own padding path."""
    for bucket in server._buckets:
        x = server._batch_input(bucket, [])
        for rung in bucket.rungs:
            jax.block_until_ready(bucket.engine(
                x, "auto", plan_override=rung.plan, rung=rung.name))


def drive(server, reqs, due, t0: float, until_s: float):
    """Serve ``reqs`` on the schedule ``due`` (seconds after ``t0``) until
    every request has terminated or ``until_s`` have passed.  Returns each
    request's lateness of submission (s), the number of batches dispatched
    and the longest queue seen."""
    annotate = jax.profiler.TraceAnnotation
    n = len(reqs)
    late = np.zeros(n)
    ticks = longest = k = 0
    while True:
        now = time.perf_counter() - t0
        if k < n and due[k] <= now:
            with annotate("perfbench.submit"):
                while k < n and due[k] <= now:
                    server.submit(reqs[k])
                    late[k] = now - due[k]
                    k += 1
        longest = max(longest, server.pending())
        if server.pending():
            with annotate("perfbench.tick"):
                ticks += server.tick() > 0
        elif k < n:
            with annotate("perfbench.sleep"):
                time.sleep(max(0.0, due[k] - (time.perf_counter() - t0)))
        else:
            break
        if now > until_s:
            break
    return late, ticks, longest


def run(r: harness.Run) -> None:
    system.import_program()
    from repro import telemetry
    from repro.serving import (BucketSpec, InferenceRequest, RobustCnnServer,
                               WallClock)

    cfg, tr = r.config, r.traffic
    c, h = cfg["channels"], cfg["image"]
    batch, pool = tr["batch"], tr["pool_images"]
    t_build = time.perf_counter()
    w, fcs, net, _, prm = system.build(cfg, r.seed)
    images = np.asarray(weights.make_images(r.seed, pool, c, h, h))
    due = arrivals.poisson_schedule(r.params["rate_per_s"], r.seconds, r.seed)
    n = len(due)
    which = np.arange(n) % pool
    control = tr.get("value_dtype")
    plan = None
    if control:
        # The program's quantised value path on the served rung, switched
        # on by a control run (``perfbench/calibrate.py``) only.
        from repro.tuning.planner import plan_program

        def plan(program, b):
            return system.narrow_plan(plan_program(
                program, batch=b, mode="roofline", params=prm,
                backend=jax.devices()[0].platform), control)
    server = RobustCnnServer(net, prm, [BucketSpec(c, h, h, batch=batch)],
                             clock=WallClock(), plan=plan)
    rungs = [g.name for g in server._buckets[0].rungs]
    harness.log(f"server: rungs {rungs}, dropped {server.dropped_rungs}")
    t_compile = time.perf_counter()
    with harness.no_cache_write():
        warm_rungs(server)
    harness.log(f"set-up: start to build {t_build - r.t_start:.3f} s, "
                f"weights, banks and ladder {t_compile - t_build:.3f} s, "
                f"one forward per rung {time.perf_counter() - t_compile:.3f} s")
    if r.traced:
        telemetry.reset()
        telemetry.enable()

    reqs = [InferenceRequest(rid=k, x=images[which[k]]) for k in range(n)]
    with harness.window(r):
        t0 = r.t_window = time.perf_counter()
        late, ticks, _ = drive(server, reqs, due, t0, r.seconds + tr["drain_s"])
        t1 = time.perf_counter()
    telemetry.disable()
    r.window_s = t1 - t0
    r.memory_peak_bytes = harness.memory_peak_bytes()
    r.attempted = n
    done = [q for q in reqs if q.status == "done"]
    lat = [(q.completed_s - (t0 + due[q.rid])) * 1e3 if q.status == "done"
           else math.inf for q in reqs]
    r.failed = n - len(done)
    r.end_to_end["p50_latency_ms"] = nearest_rank(lat, 0.50)
    r.end_to_end["p95_latency_ms"] = nearest_rank(lat, 0.95)
    rep = server.slo_report()
    r.data.update(ticks=ticks, served=len(done))
    harness.log(
        f"window: {n} requests at {r.params['rate_per_s']}/s over "
        f"{r.seconds} s, served {len(done)} in {ticks} batches, window "
        f"{r.window_s:.6f} s, set-up {r.setup_s:.3f} s; rungs "
        f"{rep.rungs_executed}, degradations {len(rep.degradations)}, "
        f"rejected {rep.rejected}")
    harness.log(f"generator lateness ms: p50 {nearest_rank(late, .5) * 1e3:.3f}"
                f" p95 {nearest_rank(late, .95) * 1e3:.3f} max "
                f"{late.max() * 1e3:.3f}")
    if r.traced:
        hist = telemetry.histogram("serving.cnn.tick_latency_s")
        r.data["tick_p50_s"] = hist.p50 if hist.count else None

    results = {q.rid: q.result for q in done}
    del server, prm, done, reqs
    jax.clear_caches()
    t0 = time.perf_counter()
    fc_w = reference.fc_weight(cfg, fcs)
    ref = correct.reference_logits(cfg, w, fc_w, images, block=32)
    rids = sorted(results)
    errs = correct.row_errors(np.stack([results[k] for k in rids]),
                              ref[which[rids]]) if rids else np.array([])
    r.numbers["max_err"] = correct.worst([errs])
    r.data.update(weights=w, fc_w=fc_w, images=images, ref=ref)
    harness.log(f"reference: {time.perf_counter() - t0:.3f} s for {pool} "
                f"images; {len(rids)} answers compared")
