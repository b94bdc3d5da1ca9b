"""Find the highest rate a serving cell's server sustains: one process,
one server, a list of offered rates, each served on its own Poisson
schedule for ``--seconds``.  A rate is sustained when the queue does not
grow over the window: the backlog left when the schedule ends drains in
about one batch, and the latency tail stays flat.  Run on the chip:

    python3 perfbench/sweep.py --workload resnet50-server-poisson \
        --seed 11 --seconds 20 --rates 8 12 16 20

Prints one JSON line per rate.  The cell's fixed rate, 0.8 of the knee, is
written into its cell file by hand.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    import numpy as np

    from perfbench import arrivals, harness, system, weights
    from perfbench.kinds import open_loop

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = harness.cell_files(args.workload, bench)
    harness.device_or_exit(int(files["cell"]["chips"]))
    harness.enable_compile_cache()
    harness.set_matmul_precision(files["config"])
    system.import_program()
    from repro.serving import (BucketSpec, InferenceRequest, RobustCnnServer,
                               WallClock)

    cfg, tr = files["config"], files["traffic"]
    c, h = cfg["channels"], cfg["image"]
    _, _, net, _, prm = system.build(cfg, args.seed)
    images = np.asarray(weights.make_images(args.seed, tr["pool_images"], c,
                                            h, h))
    server = RobustCnnServer(net, prm, [BucketSpec(c, h, h,
                                                   batch=tr["batch"])],
                             clock=WallClock())
    with harness.no_cache_write():
        open_loop.warm_rungs(server)
    rid = 0
    for rate in args.rates:
        due = arrivals.poisson_schedule(rate, args.seconds, args.seed)
        reqs = [InferenceRequest(rid=rid + k,
                                 x=images[k % len(images)])
                for k in range(len(due))]
        rid += len(due)
        t0 = time.perf_counter()
        late, ticks, longest = open_loop.drive(
            server, reqs, due, t0, args.seconds + tr["drain_s"])
        done = [q for q in reqs if q.status == "done"]
        lat = sorted((q.completed_s - (t0 + due[q.rid - reqs[0].rid])) * 1e3
                     for q in done)
        last = max((q.completed_s for q in done), default=t0) - t0
        print(json.dumps({
            "rate_per_s": rate, "requests": len(reqs), "served": len(done),
            "batches": ticks, "longest_queue": longest,
            "drain_after_schedule_s": last - args.seconds,
            "p50_ms": open_loop.nearest_rank(lat, .5) if lat else None,
            "p95_ms": open_loop.nearest_rank(lat, .95) if lat else None,
            "late_p95_ms": open_loop.nearest_rank(late, .95) * 1e3,
            "rungs": server.slo_report().rungs_executed}), flush=True)


if __name__ == "__main__":
    main()
