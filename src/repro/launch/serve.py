"""Serving driver: batched prefill + decode with KV cache (+ Escoin sparsity).

With --sparsity > 0, every linear weight is magnitude/block pruned and served
through the Escoin BCSR path (the paper's technique as a serving feature).

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b --smoke \
      --batch 4 --prompt-len 32 --gen 16 --sparsity 0.8

With --autotune, the kernel-customization autotuner (repro.tuning) plans a
CNN workload instead: per-layer method/tile selection, persisted to a JSON
plan cache, verified by a reload round-trip and an auto-vs-dense numeric
check on a reduced layer slice.

  PYTHONPATH=src python -m repro.launch.serve --smoke --autotune \
      [--cnn alexnet] [--plan-cache plans/autotune_cache.json]

With --cnn-serve, the fault-tolerant bucketed CNN serving loop
(repro.serving.robust) serves a seeded arrival trace on a reduced network
slice and prints the SLO summary; --chaos adds seeded fault injection
(repro.serving.chaos) and asserts zero lost requests plus recorded
degradation evidence.

  PYTHONPATH=src python -m repro.launch.serve --cnn-serve --chaos \
      [--cnn googlenet] [--chaos-seed 0] [--requests 40]
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs as cfgs
from repro import telemetry
from repro.core.pruning import block_prune
from repro.core.sparse_format import bcsr_from_dense, bcsr_stack_from_dense
from repro.launch.steps import make_serve_step
from repro.models import transformer as T
from repro.runtime.compile_cache import enable_compile_cache


def sparsify_params(params, cfg, sparsity: float, block=(16, 16), min_dim=64):
    """Prune + convert every large 2-D linear weight to Escoin BCSR.

    ``conv`` must fire on *every* array leaf — including leaves held in
    lists/tuples and an array at the pytree root (converting only
    dict-valued parents silently served those weights dense).
    """
    def visit(p, name=""):
        if isinstance(p, dict):
            return {k: visit(v, k) for k, v in p.items()}
        if isinstance(p, (list, tuple)):
            return type(p)(visit(v, name) for v in p)
        return conv(name, p)

    skip = {"embed", "lm_head", "router", "conv_w"}

    def conv(name, w):
        if name in skip or not hasattr(w, "ndim"):
            return w
        if w.ndim == 2 and min(w.shape) >= min_dim:
            pruned = block_prune(w.astype(jnp.float32), sparsity, block)
            # stored as (in, out); BCSR computes x @ W.T for (out, in)
            return bcsr_from_dense(np.asarray(pruned).T, block)
        if w.ndim == 3 and min(w.shape[1:]) >= min_dim:
            # stacked (L, in, out) weight inside the scanned stack
            pruned = jax.vmap(lambda m: block_prune(m, sparsity, block))(
                w.astype(jnp.float32))
            return bcsr_stack_from_dense(
                np.asarray(pruned).transpose(0, 2, 1), block)
        return w

    return visit(params)


def autotune_main(args) -> None:
    """CNN autotune flow: lower -> plan -> persist -> reload round-trip ->
    numeric check, all on the compile-once graph engine."""
    from repro.engine import CnnEngine, lower
    from repro.models import cnn
    from repro.tuning import (PlanCache, apply_plan_to_params, format_plan,
                              plan_program)

    name = args.cnn
    net = cnn.NETWORKS[name]()
    image = ({"alexnet": 99, "googlenet": 96, "resnet50": 96}[name]
             if args.smoke else 224)
    mode = args.tune_mode
    # The plan is keyed on, and run by, the device the engine binds.
    backend = jax.devices()[0].platform
    params = None
    rng = np.random.default_rng(args.seed)
    if mode == "wall":
        params = cnn.init_cnn(net, 3, rng, image)

    program = lower(net, (3, image, image))
    cache = PlanCache(args.plan_cache)
    plan = plan_program(program, batch=1, mode=mode, cache=cache,
                        params=params, backend=backend)
    fused = sum(pe.method in ("pallas", "bsr") and pe.fuse
                for pe in plan.values())
    print(f"tuned {name} @ {image}px: {program.summary()}; "
          f"{len(plan)} conv layers ({fused} fused-epilogue kernels), "
          f"{len(cache)} cache entries -> {args.plan_cache}")
    print(format_plan(plan))

    # Round-trip: a fresh cache loaded from disk must reproduce the plan
    # without re-tuning (every layer a hit).
    replan = plan_program(program, batch=1, mode=mode,
                          cache=PlanCache(args.plan_cache), params=params,
                          backend=backend)
    assert replan == plan, "plan cache reload did not reproduce the plan"
    print(f"plan cache round-trip ok ({args.plan_cache})")

    # Numeric check: auto dispatch vs the dense oracle on a reduced-channel
    # slice of the network — the first dense-kept conv plus the first two
    # sparse convs (interpret-mode Pallas stays tractable on CPU).
    convs = [l for l, _ in program.conv_table]
    picked = ([next(l for l in convs if l.sparsity == 0)]
              + [l for l in convs if l.sparsity > 0][:2])
    slice_net = []
    for l in picked:
        slice_net.append(dataclasses.replace(
            l, out_c=max(8, min(32, l.out_c // 8)), stride=1))
        slice_net.append(cnn.Relu())
    slice_prog = lower(slice_net, (3, 12, 12))
    sparams = cnn.init_cnn(slice_net, 3, rng, 12)
    x = jnp.asarray(rng.standard_normal((1, 3, 12, 12)).astype(np.float32))
    # Fresh in-memory cache: the synthetic slice geometries must not be
    # persisted into the deployment plan cache.
    splan = plan_program(slice_prog, batch=1, mode="roofline",
                         cache=PlanCache(), backend=backend)
    apply_plan_to_params(sparams, splan)
    engine = CnnEngine(slice_prog, sparams, splan)
    y_auto = engine(x, "auto")
    # Capture the auto forward's report before the dense oracle forward
    # overwrites last_report with its own.
    report = engine.last_report if telemetry.is_enabled() else None
    y_dense = engine(x, "dense")
    np.testing.assert_allclose(np.asarray(y_auto), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)
    methods = sorted({pe.method for pe in splan.values()})
    print(f"auto-vs-dense slice check ok (slice methods: {', '.join(methods)})")

    if report is not None:
        # The auto forward above recorded its per-op ExecutionReport at
        # dispatch time; surface it (and fail loudly on silent fallbacks).
        print(report.format())
        assert report.fallback_count == 0, (
            f"traced forward took {report.fallback_count} silent "
            f"fallback(s): {[o.fallback_reason for o in report.fallback_ops]}")


def cnn_serve_main(args) -> None:
    """Robust CNN serving flow: shape-bucketed admission + degradation
    ladder over a reduced network slice, driven by a seeded arrival trace
    on a virtual clock (deterministic; interpret-mode Pallas stays
    tractable on CPU).  ``--chaos`` turns on seeded fault injection — the
    run must still terminate every request (zero lost) and must leave
    degradation evidence (a ladder step-down or a dropped rung)."""
    from repro.engine import init_conv_params, lower
    from repro.serving import (BucketSpec, ChaosConfig, ChaosInjector,
                               RobustCnnServer, VirtualClock, arrival_trace,
                               slice_net)

    name = args.cnn
    net = slice_net(name)
    rng = np.random.default_rng(args.seed)
    params = init_conv_params(lower(net, (3, 12, 12)), rng)
    chaos = None
    if args.chaos:
        chaos = ChaosInjector(ChaosConfig(
            seed=args.chaos_seed, step_fault_rate=0.35,
            plan_corruption_rate=0.5, straggler_rate=0.1))
    server = RobustCnnServer(
        net, params,
        [BucketSpec(3, 12, 12, batch=2), BucketSpec(3, 16, 16, batch=2)],
        clock=VirtualClock(), queue_depth=16, max_attempts=6,
        cooldown_ticks=4, chaos=chaos)
    trace = arrival_trace(
        args.requests, [(3, 12, 12), (3, 10, 10), (3, 16, 16)],
        seed=args.seed, mean_gap_s=0.0005, deadline_s=(1.0, 2.0))
    ladder = {b.spec.key: [r.name for r in b.rungs] for b in server._buckets}
    print(f"serving {name} slice: {args.requests} requests over "
          f"{len(ladder)} buckets; ladders {ladder}"
          + (f"; chaos seed {args.chaos_seed}" if chaos else ""))
    rep = server.run_trace(trace)
    print(rep.format())
    rep.verify()  # zero lost, zero duplicated — or raise
    fatal = rep.rejected.get("fatal_error", 0)
    if chaos is None and fatal:
        # Without injected faults a fatal step is a real failure (e.g. a
        # kernel that does not compile for this device): rejecting every
        # request with a reason still terminates them, but the run failed.
        raise SystemExit(f"{fatal} request(s) failed with fatal_error")
    if chaos is not None:
        print("chaos:", chaos.summary())
        assert rep.degradations or rep.dropped_rungs, (
            "chaos run left no degradation evidence (no ladder step-down, "
            "no dropped rung) — injection did not exercise the ladder")
    print(f"slo ok: {rep.completed}/{rep.submitted} served, "
          f"{rep.rejected_total} shed with reasons, 0 lost")


def export_trace(path: str) -> None:
    """Validate + write the global tracer's Chrome-trace JSON and a metrics
    summary — what ``--trace out.json`` produces."""
    tracer = telemetry.get_tracer()
    tracer.export(path)
    print(f"exported {len(tracer)} trace events -> {path} "
          f"({len(telemetry.snapshot())} metrics recorded)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--sparsity", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--autotune", action="store_true",
                    help="run the kernel-customization autotuner (CNN path)")
    ap.add_argument("--cnn", default="alexnet",
                    choices=("alexnet", "googlenet", "resnet50"))
    ap.add_argument("--plan-cache", default="plans/autotune_cache.json")
    ap.add_argument("--tune-mode", default="roofline",
                    choices=("roofline", "wall"))
    ap.add_argument("--trace", metavar="OUT_JSON",
                    help="enable telemetry and export a Chrome-trace JSON "
                         "(chrome://tracing / Perfetto) on exit")
    ap.add_argument("--cnn-serve", action="store_true",
                    help="run the fault-tolerant bucketed CNN serving loop "
                         "(repro.serving.robust) on a reduced slice")
    ap.add_argument("--chaos", action="store_true",
                    help="with --cnn-serve: seeded fault injection "
                         "(repro.serving.chaos)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=40,
                    help="with --cnn-serve: arrival-trace length")
    args = ap.parse_args()
    enable_compile_cache()

    if args.trace:
        telemetry.enable()

    if args.autotune:
        autotune_main(args)
        if args.trace:
            export_trace(args.trace)
        return
    if args.cnn_serve:
        cnn_serve_main(args)
        if args.trace:
            export_trace(args.trace)
        return
    if not args.arch:
        ap.error("--arch is required unless --autotune is given")

    cfg = cfgs.get_config(args.arch, smoke=args.smoke)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")
    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    if args.sparsity > 0:
        params = sparsify_params(params, cfg, args.sparsity)
        print(f"serving with Escoin BCSR weights at sparsity {args.sparsity}")

    b, p, g = args.batch, args.prompt_len, args.gen
    max_len = p + g
    prompts = jax.random.randint(key, (b, p), 0, cfg.vocab, jnp.int32)
    cache = T.init_cache(cfg, b, max_len)
    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(2,))

    # prefill token-by-token (smoke-scale; production uses the prefill step)
    t0 = time.time()
    with telemetry.span("serve.prefill", tokens=p, batch=b):
        for i in range(p):
            nxt, cache = serve_step(params, prompts[:, i:i + 1], cache,
                                    jnp.int32(i))
        jax.block_until_ready(nxt)
    t_prefill = time.time() - t0

    out = [nxt]
    t0 = time.time()
    with telemetry.span("serve.decode", tokens=g - 1, batch=b):
        for i in range(p, p + g - 1):
            nxt, cache = serve_step(params, out[-1][:, None], cache,
                                    jnp.int32(i))
            out.append(nxt)
        jax.block_until_ready(out[-1])
    t_decode = time.time() - t0
    gen = np.stack([np.asarray(t) for t in out], axis=1)
    assert gen.shape == (b, g), gen.shape
    assert np.isfinite(gen).all()
    print(f"generated {g} tokens x {b} seqs; prefill {t_prefill:.2f}s, "
          f"decode {t_decode:.2f}s ({t_decode / max(g - 1, 1) * 1e3:.1f} ms/tok)")
    print("sample:", gen[0, :12].tolist())
    if args.trace:
        export_trace(args.trace)


if __name__ == "__main__":
    main()
