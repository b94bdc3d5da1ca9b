"""Closed-loop offline throughput (MLPerf Inference "Offline": every
sample is available at once, throughput is judged).

The traffic file gives the batch, the number of distinct batches in the
image pool and the engine method.  Set-up makes the weights and a pool of
distinct images on the device from the seed, binds ``CnnEngine`` and
compiles the forward with one warm-up call.  The window then dispatches
batch after batch of the pool through ``engine(x, method)``; batch i+1 is
dispatched before the host waits for batch i's logits.  Once ``seconds``
have passed no batch is dispatched, the last one is fetched, and the window
closes.  ``images_per_s`` is every image whose logits reached the host,
over the whole window.  After it, every row of logits is compared with the
reference's logits for the same image.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from perfbench import correct, counts, harness, references, system, weights

# Gaps between fetches longer than this many forwards are logged.
STALL_FORWARDS = 1.5


def run(r: harness.Run) -> None:
    cfg, tr = r.config, r.traffic
    batch, pool, method = tr["batch"], tr["pool_batches"], tr["method"]
    c, h = cfg["channels"], cfg["image"]
    t_build = time.perf_counter()
    w, fcs, _, program, prm = system.build(cfg, r.seed)
    engine = system.engine(program, prm)
    images = weights.make_images(r.seed, batch * pool, c, h, h)
    xs = [images[i * batch:(i + 1) * batch] for i in range(pool)]
    # The program's quantised value path, switched on by a control run
    # (``perfbench/calibrate.py``); the cells' own runs leave it off.
    control = tr.get("value_dtype")
    plan = (system.narrow_plan(engine._auto_plan(batch), control)
            if control else None)
    report = engine.execution_report(xs[0], method, plan_override=plan)
    by_method = system.methods(report)
    harness.log("plan: " + ", ".join(f"{k}={len(v)}"
                                      for k, v in sorted(by_method.items())))
    for o in report.ops:
        if o.method_executed in system.KERNEL_METHODS:
            harness.log(f"  {o.name}: {o.method_executed} {o.tiling}")
    if report.fallback_count:
        harness.log(f"fallbacks: {[(o.name, o.fallback_reason) for o in report.fallback_ops]}")
    t_compile = time.perf_counter()
    with harness.no_cache_write():
        jax.block_until_ready(engine(xs[0], method, plan_override=plan))
    for x in xs:
        jax.block_until_ready(x)
    harness.log(f"set-up: start to build {t_build - r.t_start:.3f} s, "
                f"weights, banks and plan {t_compile - t_build:.3f} s, "
                f"first forward {time.perf_counter() - t_compile:.3f} s")

    out = []                       # (pool index, logits on the host)
    with harness.window(r):
        t0 = r.t_window = time.perf_counter()
        pending, i, fetched = None, 0, [t0]
        while True:
            with jax.profiler.TraceAnnotation("perfbench.dispatch"):
                y = engine(xs[i % pool], method, plan_override=plan)
            if pending is not None:
                with jax.profiler.TraceAnnotation("perfbench.fetch"):
                    out.append((pending[0], np.asarray(pending[1])))
                fetched.append(time.perf_counter())
            pending, i = (i % pool, y), i + 1
            if time.perf_counter() - t0 >= r.seconds:
                break
        with jax.profiler.TraceAnnotation("perfbench.fetch"):
            out.append((pending[0], np.asarray(pending[1])))
        t1 = time.perf_counter()
    r.window_s = t1 - t0
    r.attempted = i * batch
    r.end_to_end["images_per_s"] = len(out) * batch / r.window_s
    r.memory_peak_bytes = harness.memory_peak_bytes()
    nnz = counts.nonzeros(w)
    r.data.update(forwards=len(out), batch=batch, report=report,
                  flops_per_forward=counts.forward_flops(cfg, nnz, batch),
                  nnz=nnz)
    harness.log(f"window: {len(out)} batches of {batch} in "
                f"{r.window_s:.6f} s, set-up {r.setup_s:.3f} s")
    gaps = np.diff(fetched)
    if gaps.size:
        med = float(np.median(gaps))
        long_ = [(g, t - t0) for g, t in zip(gaps, fetched[1:])
                 if g > STALL_FORWARDS * med]
        harness.log(f"fetch gaps: median {med * 1e3:.3f} ms; "
                    f"{len(long_)} over {STALL_FORWARDS}x, "
                    f"{sum(g - med for g, _ in long_) * 1e3:.1f} ms beyond it"
                    + "".join(f"; {g * 1e3:.1f} ms ending at {t:.3f} s"
                              for g, t in sorted(long_, reverse=True)[:5]))

    # The program's state goes before the reference runs.
    del engine, prm, y, pending
    jax.clear_caches()
    t0 = time.perf_counter()
    fc_ws = references.load(cfg).fc_weights(cfg, fcs)
    ref = correct.reference_logits(cfg, w, fc_ws, images, block=batch)
    errs = [correct.row_errors(y, ref[p * batch:(p + 1) * batch])
            for p, y in out]
    r.failed = int(sum(np.isinf(e).sum() for e in errs))
    r.numbers["max_err"] = correct.worst(errs)
    r.data.update(weights=w, fc_ws=fc_ws, images=images, ref=ref)
    harness.log(f"reference: {time.perf_counter() - t0:.3f} s for "
                f"{pool * batch} images; {len(out) * batch} rows compared")
