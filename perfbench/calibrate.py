"""Readings that a cell's correctness limit is set from.

For one cell, in one process on the chip: the program's number (the worst
row's error against the plain reference) on each of ``--seeds``, through
the cell's own timed path with a short window, at each matmul precision of
``--program-precisions`` (the configuration's own first); for each of
those runs, the number that the reference computed at each of
``--reference-controls`` reads in the program's place; and the number of
the program with its own quantised value path switched on
(``--value-controls``: ``int8``, ``float8_e4m3fn``) on
``--control-seeds``.  Prints one JSON line per reading::

    python3 perfbench/calibrate.py --workload resnet50-offline-b32 \\
        --seconds 16 --seeds 1 2 3 --program-precisions highest default \\
        --reference-controls high bfloat16 \\
        --value-controls float8_e4m3fn --control-seeds 1 2 3

The limits, and the readings they were set from, are in ``PERF.md``.
"""
import argparse
import copy
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def reading(files, kind, seed, seconds, precision=None, control=None):
    """One short run of the cell; returns the finished ``Run``."""
    import jax

    from perfbench import harness

    files = copy.deepcopy(files)
    if precision:
        files["config"]["matmul_precision"] = precision
    if control:
        files["traffic"]["value_dtype"] = control
    harness.set_matmul_precision(files["config"])
    run = harness.Run(seed=seed, seconds=seconds, traced=False,
                      t_start=time.perf_counter(),
                      device_kind=jax.devices()[0].device_kind, **files)
    kind.run(run)
    jax.clear_caches()
    return run


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--program-precisions", nargs="*", default=[])
    ap.add_argument("--reference-controls", nargs="*", default=[])
    ap.add_argument("--value-controls", "--controls", nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from perfbench import correct, harness

    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    files = harness.cell_files(args.workload, bench)
    harness.device_or_exit(int(files["cell"]["chips"]))
    harness.enable_compile_cache()
    kind = importlib.import_module(
        "perfbench.kinds." + files["traffic"]["kind"])
    precisions = args.program_precisions or [None]
    jobs = [(s, p, None) for s in args.seeds for p in precisions]
    jobs += [(s, None, c) for c in args.value_controls
             for s in args.control_seeds]
    for seed, precision, control in jobs:
        line = {"workload": args.workload, "seed": seed,
                "precision": precision or files["config"].get(
                    "matmul_precision", "default"),
                "control": control or "program"}
        try:
            run = reading(files, kind, seed, args.seconds, precision,
                          control)
        except Exception as exc:  # noqa: BLE001 - a crashed control is a reading
            line["error"] = f"{type(exc).__name__}: {exc}"[:500]
            print(json.dumps(line), flush=True)
            continue
        line.update(max_err=run.numbers["max_err"], failed=run.failed,
                    compiles_in_window=run.compiles_in_window,
                    setup_s=run.setup_s, **run.end_to_end)
        d = run.data
        if control is None and d.get("ref") is not None:
            for ref_prec in args.reference_controls:
                t0 = time.perf_counter()
                y = correct.reference_logits(
                    run.config, d["weights"], d["fc_w"], d["images"],
                    precision=ref_prec)
                line[f"reference_{ref_prec}_max_err"] = float(
                    correct.row_errors(y, d["ref"]).max())
                line[f"reference_{ref_prec}_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
