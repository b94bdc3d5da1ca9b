"""Paper Fig. 8: execution time of sparse CONV layers, per model x method,
normalized to the dense (CUBLAS-analogue) approach.

Methods: dense (CUBLAS), lowered (CUSPARSE: im2col + CSR SpMM), csr-direct
(Escoin, pure-JAX direct sparse conv).  The Pallas kernels (the ELL VPU
path and the BCSR ``bsr`` MXU path) run in interpret mode on CPU
(Python-executed), so their wall times are *not* comparable — their
performance cases are made by the roofline model; the bsr row reports its
projected MXU-vs-dense speedup from that model.

CPU wall-times do not reproduce GPU magnitudes; the comparison of *methods*
on identical shapes/sparsities is the reproduction target.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row, time_fn
from repro.core import dense_conv, direct_sparse_conv, lowered_sparse_conv
from repro.core.pruning import magnitude_prune
from repro.core.sparse_format import ell_from_dense, ell_from_dense_conv
from repro.models import cnn

# reduced-scale geometry for CPU timing (methods see identical shapes)
SCALES = {"alexnet": (99, 4), "googlenet": (96, 2), "resnet50": (96, 2)}

# Strided sparse layers (reduced-scale stand-ins for AlexNet conv1-class
# stride-4 stems and ResNet stride-2 bottleneck entries).  These are the
# layers the old Pallas kernel refused (stride != 1 fell back to pure JAX);
# the spatially-tiled kernel runs them in-kernel, so they get their own
# fig8 rows: (name, C, H, M, R, stride, pad, sparsity).
STRIDED_LAYERS = [
    ("stem_s4", 3, 99, 96, 11, 4, 0, 0.80),
    ("res_s2", 64, 48, 64, 3, 2, 1, 0.70),
]


def bench_model(name: str, *, iters: int = 3, autotune: bool = False) -> List[str]:
    image, batch = SCALES[name]
    net = cnn.NETWORKS[name]()
    rng = np.random.default_rng(0)
    params = cnn.init_cnn(net, 3, rng, image)
    shapes = cnn.conv_layer_shapes(net, 3, image)
    totals: Dict[str, float] = {"dense": 0.0, "lowered": 0.0, "csr-direct": 0.0}
    for layer, (c, h, w) in shapes:
        if layer.sparsity == 0:
            continue  # paper: only sparse CONV layers in this figure
        x = jnp.asarray(rng.standard_normal((batch, c, h, w)).astype(np.float32))
        entry = params[layer.name]
        fns = {
            "dense": jax.jit(functools.partial(
                dense_conv, stride=layer.stride, padding=layer.pad)),
            "lowered": jax.jit(functools.partial(
                lowered_sparse_conv, r=layer.k, s=layer.k,
                stride=layer.stride, padding=layer.pad)),
            "csr-direct": jax.jit(functools.partial(
                direct_sparse_conv, stride=layer.stride, padding=layer.pad)),
        }
        args = {"dense": (x, entry["w"]), "lowered": (x, entry["ell2d"]),
                "csr-direct": (x, entry["ell"])}
        for m in totals:
            totals[m] += time_fn(fns[m], *args[m], warmup=1, iters=iters)
    # Analytic TPU projection per method, summed over the sparse layers at
    # the paper's full 224px geometry and batch 128.  All rows come from
    # ONE model — the tuner's roofline (`tuning.measure.roofline_estimate`,
    # MXU peak for dense/bsr contractions, VPU FMA rate for the per-nonzero
    # loops) — so the figure's projected speedups are mutually comparable;
    # the old hand-rolled flat-peak formulas priced every method at the MXU
    # peak and overstated the scan paths ~8x relative to the bsr row.  The
    # bsr row is roofline-only (interpret-mode wall time is not comparable,
    # same policy as the ELL Pallas kernel) and assumes block-structured
    # pruning at each layer's sparsity — the flexibility the BCSR path
    # trades for MXU throughput.
    from repro.tuning import Candidate, roofline_estimate
    from benchmarks.bench_sparse_conv import best_bsr_candidate, layer_geometry
    proj = {"dense": 0.0, "lowered": 0.0, "csr-direct": 0.0}
    t_bsr_rf = 0.0
    full_shapes = cnn.conv_layer_shapes(net, 3, 224)
    for layer, (c, h, w) in full_shapes:
        if layer.sparsity == 0:
            continue
        g = layer_geometry(layer, c, h, w, batch=128)  # paper batch
        for m in proj:
            proj[m] += roofline_estimate(
                g, Candidate(m, pad_to=None if m == "dense" else 8))
        cand = best_bsr_candidate(g)
        if cand is not None:
            t_bsr_rf += roofline_estimate(g, cand)
    out = []
    base = totals["dense"]
    for m, t in totals.items():
        out.append(row(
            f"fig8/{name}/{m}", t,
            f"speedup_vs_dense={base / t:.2f};"
            f"tpu_projected_speedup={proj['dense'] / proj[m]:.2f}"))
    if t_bsr_rf:
        out.append(row(
            f"fig8/{name}/bsr", t_bsr_rf,
            f"roofline_only=1;"
            f"tpu_projected_speedup={proj['dense'] / t_bsr_rf:.2f}"))
    if autotune:
        # Measurement-driven per-layer method selection (repro.tuning): the
        # tuned total is the sum of each sparse layer's winning wall time
        # (epilogue included — the tuner times conv+bias/ReLU/shortcut as
        # one unit since the fused kernel executes them as one).  The dense
        # baseline for this row is therefore re-measured epilogue-inclusive:
        # dividing the conv-only `base` by an epilogue-inclusive tuned total
        # would understate the tuned speedup.
        from repro.engine import lower
        from repro.tuning import (Candidate, PlanCache, geometry_of_op,
                                  measure_candidate, plan_program)
        program = lower(net, (3, image, image))
        plan = plan_program(program, batch=batch, mode="wall",
                            cache=PlanCache(), params=params, iters=iters,
                            backend=jax.devices()[0].platform)
        t_auto = t_dense_epi = 0.0
        for op in program.conv_ops:
            if op.sparsity == 0:
                continue
            t_auto += plan[op.name].est_s
            g = geometry_of_op(op, batch=batch)
            x = jnp.asarray(rng.standard_normal(
                (batch, op.c, op.h, op.w)).astype(np.float32))
            t_dense_epi += measure_candidate(
                g, Candidate("dense"), np.asarray(params[op.name]["w"]), x,
                iters=iters)
        out.append(row(f"fig8/{name}/auto", t_auto,
                       f"speedup_vs_dense={t_dense_epi / t_auto:.2f}"))
    return out


def bench_strided(*, iters: int = 3, batch: int = 2) -> List[str]:
    """Per-method wall rows for strided sparse layers (stride 2 and 4).

    The Pallas kernel itself is interpret-mode on CPU (not wall-comparable,
    same policy as the per-model rows); its strided coverage is exercised by
    the tier-1 parity tests and ranked by the tuner's roofline model.
    """
    rng = np.random.default_rng(0)
    out: List[str] = []
    for name, c, h, m, r, stride, pad, sp in STRIDED_LAYERS:
        x = jnp.asarray(rng.standard_normal((batch, c, h, h)).astype(np.float32))
        wt = np.asarray(magnitude_prune(jnp.asarray(
            rng.standard_normal((m, c, r, r)).astype(np.float32)), sp))
        ell = ell_from_dense_conv(wt)
        ell2d = ell_from_dense(wt.reshape(m, -1))
        fns = {
            "dense": jax.jit(functools.partial(
                dense_conv, stride=stride, padding=pad)),
            "lowered": jax.jit(functools.partial(
                lowered_sparse_conv, r=r, s=r, stride=stride, padding=pad)),
            "csr-direct": jax.jit(functools.partial(
                direct_sparse_conv, stride=stride, padding=pad)),
        }
        args = {"dense": (x, jnp.asarray(wt)), "lowered": (x, ell2d),
                "csr-direct": (x, ell)}
        base = None
        for meth in ("dense", "lowered", "csr-direct"):
            t = time_fn(fns[meth], *args[meth], warmup=1, iters=iters)
            base = t if base is None else base
            out.append(row(
                f"fig8/strided/{name}/{meth}", t,
                f"stride={stride};speedup_vs_dense={base / t:.2f}"))
    return out


def run(autotune: bool = False) -> List[str]:
    lines = []
    for name in SCALES:
        lines += bench_model(name, autotune=autotune)
    lines += bench_strided()
    return lines
