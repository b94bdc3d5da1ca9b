"""Beyond-paper Fig. 12: tuned-vs-fixed speedup per layer.

For every model, the autotuner (``repro.tuning``) measures each candidate
(method x (tm, te) x pad_to x fuse) per *distinct* sparse conv geometry
and picks a winner; this table reports, per geometry, the tuned wall time
against each fixed single-method baseline — the measured counterpart of the
paper's kernel-customization table (§3.3-3.4).

Geometries come from the engine's lowered program, so the dedup key carries
each conv's fused-epilogue signature: a bottleneck tail (fused shortcut)
and a plain conv+ReLU with the same shape are distinct rows, exactly like
the planner's cache.  Duplicate geometries (repeated ResNet bottlenecks,
inception twins) share a key and are reported once; totals weight each
geometry by its occurrence count.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import row
from benchmarks.fig8_sparse_conv import SCALES
from repro.engine import lower
from repro.models import cnn
from repro.tuning import (Candidate, PlanCache, geometry_of_op, layer_key,
                          measure_candidate, plan_program)

FIXED = ("dense", "lowered", "csr-direct")


def bench_model(name: str, *, iters: int = 3) -> List[str]:
    image, batch = SCALES[name]
    net = cnn.NETWORKS[name]()
    rng = np.random.default_rng(0)
    params = cnn.init_cnn(net, 3, rng, image)
    program = lower(net, (3, image, image))
    cache = PlanCache()
    plan = plan_program(program, batch=batch, mode="wall",
                        cache=cache, params=params, iters=iters,
                        backend=jax.devices()[0].platform)
    lines: List[str] = []
    seen: Dict[str, Dict[str, float]] = {}
    totals = {m: 0.0 for m in FIXED}
    t_tuned = 0.0
    for op in program.conv_ops:
        if op.sparsity == 0:
            continue
        g = geometry_of_op(op, batch=batch)
        key = layer_key(g, "cpu")
        if key in seen:
            fixed = seen[key]
        else:
            x = jnp.asarray(rng.standard_normal(
                (batch, op.c, op.h, op.w)).astype(np.float32))
            wd = np.asarray(params[op.name]["w"])
            fixed = {
                m: measure_candidate(
                    g, Candidate(m, pad_to=None if m == "dense" else 8),
                    wd, x, iters=iters)
                for m in FIXED}
            seen[key] = fixed
            pe = plan[op.name]
            best_fixed = min(fixed.values())
            lines.append(row(
                f"fig12/{name}/{op.name}", pe.est_s,
                f"method={pe.method};tm={pe.tm or '-'};"
                f"te={pe.te or '-'};"
                f"pad_to={pe.pad_to or '-'};fuse={int(pe.fuse)};"
                f"stride={op.stride};"
                f"speedup_vs_dense={fixed['dense'] / pe.est_s:.2f};"
                f"speedup_vs_best_fixed={best_fixed / pe.est_s:.2f}"))
        for m in FIXED:
            totals[m] += fixed[m]
        t_tuned += plan[op.name].est_s
    for m in FIXED:
        lines.append(row(f"fig12/{name}/total/{m}", totals[m],
                         f"tuned_speedup={totals[m] / t_tuned:.2f}"))
    lines.append(row(f"fig12/{name}/total/auto", t_tuned,
                     f"speedup_vs_dense={totals['dense'] / t_tuned:.2f}"))
    return lines


def run() -> List[str]:
    lines = []
    for name in SCALES:
        lines += bench_model(name)
    return lines
