"""Run one cell of ``BENCHMARK.json`` on the chip this machine holds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

With ``--trace 0`` the last line of standard output is the cell's
end-to-end metrics; with ``--trace 1`` the window is recorded by the
profiler and the line carries the per-layer metrics, the device's busy
time and a breakdown.  Either way it says whether every answer of the
window agreed with the plain reference, and the last lines of standard
error give each number compared beside its limit.  Without an accelerator,
or with fewer chips than the cell asks for, it exits 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# The TPU compiler logs under /tmp unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def execute(name: str, seed: int, seconds: float, traced: bool,
            keep_trace=None):
    """One run of cell ``name``; returns the result line as a dict."""
    from perfbench import harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = harness.cell_files(name, bench)
    harness.device_or_exit(int(files["cell"]["chips"]))
    harness.enable_compile_cache()
    harness.set_matmul_precision(files["config"])
    import jax
    run = harness.Run(seed=seed, seconds=seconds, traced=traced,
                      t_start=T_START, keep_trace=keep_trace,
                      device_kind=jax.devices()[0].device_kind, **files)
    kind = importlib.import_module(
        "perfbench.kinds." + files["traffic"]["kind"])
    kind.run(run)
    e2e, per = harness.cell_metrics(name, bench)
    return harness.result(run, e2e, per)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the profiler's trace into this directory")
    args = ap.parse_args(argv)
    out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                  keep_trace=args.keep_trace)
    for k, v in out["checks"].items():
        print(f"check: {k}={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
