"""Smoke check of the main path on one TPU chip.

Pruned ResNet-50 at its published 224x224 input, batch 8, random weights
from ``--seed``, through the entry points a user calls: ``lower`` and
``cnn.init_cnn``, ``CnnEngine`` and ``RobustCnnServer``.  Run from the
root of a checkout:

    python chip_smoke.py [--seed 0]

Phases, in order; any failure raises and the script exits non-zero without
printing its last line:

  device   ``jax.devices()[0]`` must be a TPU — there is no CPU fallback.
  probe    each conv kernel once at tiny shapes — ELL and BCSR, strides 1
           and 2, 3x3 and 1x1, blocking and pipelined staging, the ELL
           pointwise schedule, an int8 bank and a fused residual — against
           XLA's convolution at highest precision.  Their halo bands are 11 to 14 rows, which the
           kernels stage as whole 16-row sublane tiles; a partial-tile
           staging copy is what hangs on a v5e, so a regression fails here
           in seconds, before the 224-px forwards.
  forward  the 224-px batch-8 forward under ``pallas`` (ELL kernel),
           ``bsr`` (BCSR kernel) and ``auto`` (a roofline plan from an
           in-memory ``PlanCache``), each checked against ``dense`` run
           under ``jax.default_matmul_precision("highest")``; each compiled
           program must hold one ``tpu_custom_call`` per layer its
           ExecutionReport says ran on a Pallas kernel; ``auto`` must have
           no fallbacks, and each fallback of a forced method is printed
           with its reason.
  server   a ``RobustCnnServer`` (wall clock, one (3, 224, 224) bucket at
           batch 8, no chaos) serves 32 requests: all complete on the
           ``tuned`` rung, with no degradation, no dropped rung and no
           ``fatal_error``, and the first batch matches the reference.

Every phase runs under a watchdog (``faulthandler``): a phase that
outlasts its budget, or the run's deadline, dumps every thread's stack to
standard error and exits 1, so a hung kernel names itself instead of
holding the chip.  Times printed on the way are smoke timings of one cold
process, not metrics.  The last line of standard output is one JSON object
naming the device JAX reports.
"""
from __future__ import annotations

import argparse
import contextlib
import faulthandler
import json
import os
import sys
import time
import warnings

# The TPU compiler logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

NET = "resnet50"
IMAGE = 224
BATCH = 8
REQUESTS = 32

# Tolerance on max|y - ref| / max|ref| over the logits, against ``dense``
# run at ``jax.default_matmul_precision("highest")``.  The forwards run at
# JAX's default precision, where XLA's TPU convolutions (conv1, res2, every
# projection shortcut, and the FC layer — in all methods) may multiply f32
# in one bf16 pass: an 8-bit mantissa per operand.  Rounding every conv and
# FC operand of this network to bf16 moves its logits by 0.42% of
# max|ref| (emulated on the CPU at batch 2); the ELL kernel (``pallas``)
# adds only f32 FMA rounding on the VPU, and the BCSR kernel's in-kernel
# matmul (``bsr``, and the ``auto`` plan's BCSR layers) at most the same
# single bf16 pass.  2e-2 leaves a factor of about 5 over that floor; a
# wrong index, window, stride or epilogue gives an error of order 1.
TOLERANCE = {"dense": 0.02, "pallas": 0.02, "bsr": 0.02, "auto": 0.02}

KERNEL_METHODS = ("pallas", "bsr")

# Seconds from start by which every phase must be done; a cold run takes a
# few minutes.  Each phase also has its own budget, compile included.
DEADLINE = 1140.0
_START = time.monotonic()

# Tiny probes: (kernel, C, H, M, R, stride, pad, value dtype, pipeline,
# fused residual).  Staged halo rows (``(E-1)*stride + R``) are 12, 11, 12
# and 14 — none a whole sublane tile before ``window.stage_shape`` rounds
# them up.  The ELL 1x1 stride-1 probes run the pointwise schedule: 288
# pixels on 3 rows of 128 lanes, padded to one 8-row tile.
PROBES = (
    ("pallas", 8, 10, 16, 3, 1, 1, "float32", False, False),
    ("pallas", 8, 10, 16, 3, 1, 1, "float32", True, False),
    ("pallas", 16, 11, 16, 1, 2, 0, "float32", None, False),
    ("pallas", 16, 12, 16, 1, 1, 0, "float32", None, False),
    ("pallas", 16, 12, 16, 1, 1, 0, "int8", None, True),
    ("bsr", 16, 12, 16, 3, 1, 1, "float32", None, False),
    ("bsr", 16, 11, 16, 1, 2, 0, "float32", None, False),
    ("bsr", 16, 12, 16, 3, 1, 1, "int8", None, True),
)
# Tolerance of a probe on max|y - ref| / max|ref|: the ELL kernel adds
# only f32 FMA rounding; the BCSR kernel's MXU matmul may take f32 operands
# in one bf16 pass (see TOLERANCE).
PROBE_TOLERANCE = {"pallas": 1e-4, "bsr": 2e-2}


def tpu_device():
    """The first JAX device, or exit non-zero when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        raise SystemExit(2)
    return dev


@contextlib.contextmanager
def watchdog(name: str, budget: float):
    """Run a phase under a hang watchdog: past ``budget`` seconds, or past
    the run's ``DEADLINE``, dump every thread's stack and exit 1."""
    limit = max(1.0, min(budget, DEADLINE - (time.monotonic() - _START)))
    print(f"[{name}] watchdog {limit:.0f} s")
    faulthandler.dump_traceback_later(limit, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def check(ok, what) -> None:
    """Fail the run unless ``ok``: a check that ``python -O`` keeps."""
    if not ok:
        raise SystemExit(f"chip_smoke: {what}")


def rel_err(y, ref) -> float:
    import numpy as np

    y, ref = np.asarray(y, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(y - ref)) / np.max(np.abs(ref)))


def check_output(name: str, y, ref, tol: float) -> float:
    import numpy as np

    y = np.asarray(np.stack(y) if isinstance(y, list) else y)
    check(y.shape == ref.shape, f"{name}: shape {y.shape} != {ref.shape}")
    check(np.isfinite(y).all(), f"{name}: non-finite outputs")
    err = rel_err(y, ref)
    print(f"  {name}: max|y-ref|/max|ref| = {err:.3e} (tolerance {tol})")
    check(err <= tol, f"{name}: error {err:.3e} over tolerance {tol}")
    return err


def probe_phase(seed: int) -> None:
    """Each conv kernel once at the tiny ``PROBES`` shapes, on the chip (no
    interpret mode, no fallback), against ``dense_conv`` at highest
    precision with the same epilogue."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import (bcsr_conv_from_dense, dense_conv,
                            ell_from_dense_conv, magnitude_prune,
                            quantize_values)
    from repro.core.sparse_format import bcsr_conv_to_dense, dequantize
    from repro.kernels.bsr_conv.ops import bsr_conv
    from repro.kernels.sparse_conv.ops import sparse_conv
    from repro.telemetry.fallback import SparseFallbackWarning

    def dense_weights(kernel, bank) -> np.ndarray:
        """The (M, C, R, S) weights a (possibly quantised) bank holds."""
        bank = dequantize(bank)
        if kernel == "bsr":
            return np.asarray(bcsr_conv_to_dense(bank))
        w = np.zeros(bank.shape, np.float32)
        rows = np.arange(bank.shape[0])[:, None]
        # ELL padding entries hold zeros, so adding them is inert.
        np.add.at(w, (rows, np.asarray(bank.cidx), np.asarray(bank.ridx),
                      np.asarray(bank.sidx)), np.asarray(bank.value))
        return w

    rng = np.random.default_rng(seed)
    for kernel, c, h, m, r, stride, pad, vdtype, pipeline, res in PROBES:
        name = (f"{kernel} {r}x{r} stride {stride} {vdtype} "
                f"pipeline={pipeline} residual={res}")
        w = np.asarray(magnitude_prune(jnp.asarray(
            rng.standard_normal((m, c, r, r)).astype(np.float32)), 0.7))
        x = jnp.asarray(rng.standard_normal((2, c, h, h)).astype(np.float32))
        bias = jnp.asarray(rng.standard_normal(m).astype(np.float32))
        e = (h + 2 * pad - r) // stride + 1
        shortcut = (jnp.asarray(rng.standard_normal((2, m, e, e))
                                .astype(np.float32)) if res else None)
        if kernel == "pallas":
            bank = ell_from_dense_conv(w)
        else:
            bank = bcsr_conv_from_dense(w, block=(8, 128))
        if vdtype != "float32":
            bank = quantize_values(bank, vdtype)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always", SparseFallbackWarning)
            t0 = time.perf_counter()
            if kernel == "pallas":
                y = sparse_conv(x, bank, stride=stride, padding=pad,
                                bias=bias, fuse_relu=True, residual=shortcut,
                                pipeline=pipeline, layer=name)
            else:
                y = bsr_conv(x, bank, stride=stride, padding=pad, bias=bias,
                             fuse_relu=True, residual=shortcut, layer=name)
            y = np.asarray(jax.block_until_ready(y))
            took = time.perf_counter() - t0
        fell = [str(rec.message) for rec in caught
                if issubclass(rec.category, SparseFallbackWarning)]
        check(not fell, f"probe {name}: {fell}")
        with jax.default_matmul_precision("highest"):
            ref = dense_conv(x, jnp.asarray(dense_weights(kernel, bank)),
                             stride=stride, padding=pad)
            ref = ref + bias[None, :, None, None]
            if res:
                ref = ref + shortcut
            ref = np.asarray(jnp.maximum(ref, 0.0))
        print(f"probe {name}: {took:.2f} s incl. compile (smoke timing)")
        check_output(f"probe {name}", y, ref, PROBE_TOLERANCE[kernel])


def forward_phase(program, params, x, ref, batch: int) -> None:
    """Each method's forward in turn, under its own watchdog."""
    from repro.engine import CnnEngine
    from repro.tuning import PlanCache
    from repro.tuning.planner import plan_program

    engine = CnnEngine(program, params)
    check(not engine.interpret, "engine would interpret kernels on a TPU")
    plan = plan_program(program, batch=batch, mode="roofline",
                        cache=PlanCache(), params=params,
                        backend=engine.platform)
    for method in ("dense",) + KERNEL_METHODS + ("auto",):
        with watchdog(f"forward {method}", 300):
            forward_method(engine, method, plan if method == "auto" else None,
                           x, ref)


def forward_method(engine, method: str, override, x, ref) -> None:
    """One method's forward: cold and warm calls, its AOT program, its
    output against ``ref``, its kernel layers and fallbacks."""
    import jax

    t0 = time.perf_counter()
    y = jax.block_until_ready(engine(x, method, plan_override=override))
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(engine(x, method, plan_override=override))
    warm = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = engine.lowered(x, method, plan_override=override).compile()
    aot = time.perf_counter() - t0
    code_mb = compiled.memory_analysis().generated_code_size_in_bytes / 2**20
    print(f"{method}: smoke timing (not a metric): first call "
          f"{cold:.2f} s incl. compile, warm call {warm:.3f} s, AOT "
          f"recompile {aot:.2f} s, program {code_mb:.1f} MiB")
    check_output(method, y, ref, TOLERANCE[method])
    report = engine.execution_report(x, method, plan_override=override)
    on_kernel = [o for o in report.ops
                 if o.method_executed in KERNEL_METHODS]
    by_kernel: dict = {}
    for o in report.ops:
        by_kernel.setdefault(o.method_executed, []).append(o.name)
    print("  layers per kernel: " + "; ".join(
        f"{k}={len(v)}" for k, v in sorted(by_kernel.items())))
    for k in KERNEL_METHODS:
        if k in by_kernel:
            print(f"    {k}: {' '.join(by_kernel[k])}")
    for o in report.fallback_ops:
        print(f"  fallback: {o.name} {o.method_planned} -> "
              f"{o.method_executed} ({o.fallback_reason})")
    if method == "auto":
        check(report.fallback_count == 0, (
            f"auto fell back on {report.fallback_count} layer(s)"))
    calls = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    print(f"  tpu_custom_call: {calls} in the program, {len(on_kernel)} "
          f"layers on a Pallas kernel")
    check(calls == len(on_kernel), (
        f"{method}: {calls} tpu_custom_call(s) for {len(on_kernel)} "
        "kernel layers"))
    if method in KERNEL_METHODS + ("auto",):
        check(on_kernel, f"{method}: no layer ran on a Pallas kernel")


def server_phase(net, params, images, ref) -> None:
    """Serve REQUESTS requests through one (3, IMAGE, IMAGE) bucket."""
    from repro.serving import (BucketSpec, InferenceRequest, RobustCnnServer,
                               WallClock)

    t0 = time.perf_counter()
    server = RobustCnnServer(net, params, [BucketSpec(3, IMAGE, IMAGE,
                                                      batch=BATCH)],
                             clock=WallClock(), queue_depth=2 * REQUESTS)
    print(f"server: built in {time.perf_counter() - t0:.2f} s (smoke "
          "timing)")
    check(not server.dropped_rungs, f"dropped rungs: {server.dropped_rungs}")
    reqs = [InferenceRequest(rid=i, x=images[i % len(images)],
                             shape=images[0].shape)
            for i in range(REQUESTS)]
    for req in reqs:
        check(server.submit(req), f"request {req.rid} rejected at admission")
    t0 = time.perf_counter()
    for _ in range(4 * REQUESTS):
        if not server.pending():
            break
        server.tick()
    print(f"server: served in {time.perf_counter() - t0:.2f} s incl. compile "
          "(smoke timing)")
    rep = server.slo_report().verify()
    print(rep.format())
    check(rep.completed == rep.submitted == REQUESTS, rep.format())
    check(set(rep.rungs_executed) == {"tuned"}, rep.rungs_executed)
    check(not rep.degradations, rep.degradations)
    check(not rep.dropped_rungs, rep.dropped_rungs)
    check(not rep.rejected.get("fatal_error"), rep.rejected)
    first = [req.result for req in reqs[:len(images)]]
    check_output("server tuned rung", first, ref, TOLERANCE["auto"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and images")
    args = ap.parse_args()

    # Line-buffered, so what was printed survives a watchdog's exit.
    sys.stdout.reconfigure(line_buffering=True)
    with watchdog("device", 120):
        dev = tpu_device()
    import jax
    import numpy as np

    count = len(jax.devices())
    print(f"device: {dev.platform} {dev.device_kind} (x{count})")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.engine import CnnEngine, lower
    from repro.models import cnn
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    with watchdog("probe", 240):
        probe_phase(args.seed)
    net = cnn.NETWORKS[NET]()
    rng = np.random.default_rng(args.seed)
    params = cnn.init_cnn(net, 3, rng, image=IMAGE)
    program = lower(net, (3, IMAGE, IMAGE))
    images = rng.standard_normal((BATCH, 3, IMAGE, IMAGE)).astype(np.float32)
    x = jax.device_put(images)
    t0 = time.perf_counter()
    with watchdog("reference", 240), jax.default_matmul_precision("highest"):
        ref = np.asarray(CnnEngine(program, params)(x, "dense"))
    print(f"reference: dense at highest precision, "
          f"{time.perf_counter() - t0:.2f} s incl. compile (smoke timing), "
          f"logits {ref.shape}")
    forward_phase(program, params, x, ref, BATCH)
    with watchdog("server", 480):
        server_phase(net, params, images, ref)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))


if __name__ == "__main__":
    main()
