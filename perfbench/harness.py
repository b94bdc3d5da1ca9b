"""What every cell shares: the files a cell is made of, the device check,
the compilation cache, the measured window, and the result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.  The
harness finds each by name: ``configs/<config>.json``,
``traffic/<traffic>.json`` (whose ``kind`` names the module that drives it,
``kinds/<kind>.py``), ``cells/<cell>.json`` (the cell's own parameters and
correctness limits) and ``metrics/<metric>.py`` for each per-layer metric
(a ``read(run)`` that returns the number, or None when it finds nothing to
read).  Adding a cell of an existing configuration and mix adds one file.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

from perfbench import correct, trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = os.path.join(ROOT, ".perfbench")


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """One run of one cell: its inputs, and what the module of its traffic
    kind leaves for the metric readers."""

    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    params: Dict[str, Any]          # the cell file
    seed: int
    seconds: float
    traced: bool
    t_start: float
    keep_trace: Optional[str] = None  # directory to copy the trace to
    device_kind: str = ""
    # Filled by the traffic kind's module.
    t_window: Optional[float] = None
    window_s: Optional[float] = None
    end_to_end: Dict[str, float] = dataclasses.field(default_factory=dict)
    numbers: Dict[str, float] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    trace: Optional[trace.Trace] = None
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def setup_s(self) -> float:
        return self.t_window - self.t_start


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def cell_files(name: str, bench: Dict[str, Any]) -> Dict[str, Any]:
    """The cell's entry of ``BENCHMARK.json`` and the files it names."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return {
        "cell": cell,
        "config": load_json(os.path.join(ROOT, configs[cell["config"]]["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "params": load_json(os.path.join(HERE, "cells", name + ".json")),
    }


def cell_metrics(name: str, bench: Dict[str, Any]):
    """The end-to-end and per-layer metric entries that the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per = [m for m in bench["per_layer"]
           if (name in m["workloads"] if "workloads" in m
               else m["moves"] in names)]
    return e2e, per


def device_or_exit(chips: int):
    """JAX's devices, or exit 3 with nothing on standard output when JAX
    finds no accelerator or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < chips:
        log(f"perfbench: the cell needs {chips} accelerator chip(s); JAX "
            f"finds {len(devs)} {devs[0].platform} device(s)")
        raise SystemExit(3)
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at ``.jax_cache`` in the root of
    the checkout whatever the environment says, so that two checkouts on
    one machine share nothing and only a cell's first run compiles the
    benchmark's own programs."""
    import jax
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def set_matmul_precision(config: Dict[str, Any]) -> None:
    """Run the program's matmuls and convolutions at the precision the
    configuration states (``matmul_precision``: ``default``, the TPU's one
    bfloat16 pass, or ``highest``).  The reference names its precision on
    every operation and does not depend on this."""
    import jax
    p = config.get("matmul_precision", "default")
    jax.config.update("jax_default_matmul_precision",
                      None if p == "default" else p)


@contextlib.contextmanager
def no_cache_write():
    """Compile without writing to the persistent cache: for the program's
    forwards, which hold the run's weights as constants, so no other seed
    could ever read the entry back."""
    import jax
    old = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old)


class CompileCounter:
    """Counts the traces and backend compiles JAX reports."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _duration: float, **_kw) -> None:
        if event in self.EVENTS:
            self.count += 1


_COUNTER: Optional[CompileCounter] = None


@contextlib.contextmanager
def window(run: Run):
    """The measured window: counts the compiles inside it, records it with
    the profiler when the run is traced, and marks it on the trace's host
    timeline as ``perfbench.window``."""
    import jax
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    trace_dir = os.path.join(WORK_DIR, f"trace-{run.cell['name']}")
    if run.traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir)
    before = _COUNTER.count
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            yield
    finally:
        run.compiles_in_window = _COUNTER.count - before
        if run.traced:
            jax.profiler.stop_trace()
            path = trace.find_xplane(trace_dir)
            if run.keep_trace:
                os.makedirs(run.keep_trace, exist_ok=True)
                shutil.copy(path, os.path.join(
                    run.keep_trace,
                    f"{run.cell['name']}-{run.seed}.xplane.pb"))
            t0 = time.perf_counter()
            run.trace = trace.load(path)
            log(f"trace: {os.path.getsize(path)} bytes, read in "
                f"{time.perf_counter() - t0:.3f} s")
            shutil.rmtree(trace_dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def result(run: Run, e2e: List[Dict[str, Any]], per: List[Dict[str, Any]]
           ) -> Dict[str, Any]:
    """The result line: correct, counts, metrics, device, breakdown, and
    last the numbers compared with their limits."""
    import jax
    limits = dict(run.params["limits"])
    numbers = dict(run.numbers, compiles_in_window=run.compiles_in_window,
                   failed=run.failed)
    limits.update(compiles_in_window=0, failed=0)
    ok = correct.passes(numbers, limits)
    metrics: Dict[str, Any] = {}
    if run.traced:
        for m in per:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        for m in e2e:
            v = values[m["name"]]
            metrics[m["name"]] = {
                "value": _finite(v), "unit": m["unit"]}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(run.cell["chips"]),
              "memory_peak_bytes": run.memory_peak_bytes}
    out: Dict[str, Any] = {"correct": bool(ok), "attempted": run.attempted,
                           "failed": run.failed, "metrics": metrics,
                           "device": device}
    if run.traced and run.trace is not None:
        device["busy_s"] = trace.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": trace.top_ops(run.trace),
                            "idle_gaps": trace.idle_gaps(run.trace)}
    out["checks"] = {k: {"value": _finite(numbers.get(k)), "limit": limits[k]}
                     for k in sorted(limits)}
    return out


def _finite(v):
    return v if v is not None and math.isfinite(v) else None
