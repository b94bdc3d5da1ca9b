"""Share of its roofline that one Pallas conv kernel reaches in a traced
offline window, in percent.

The least time the chip needs for the layers that the ExecutionReport says
ran on the kernel (each layer the larger of its required operations over
the bf16 peak and its required bytes over HBM bandwidth,
``perfbench.counts``), over that kernel's device time in the trace, per
forward.  None when no layer ran on the kernel or the trace holds none of
its events.
"""
from __future__ import annotations

from typing import Optional

from perfbench import counts, peaks, references, trace

# The engine method that runs a layer on each kernel, by the kernel's name
# in the trace.
METHOD = {"sparse_conv": "pallas", "bsr_conv": "bsr"}


def share(run, kernel: str) -> Optional[float]:
    if run.trace is None or not run.data.get("forwards"):
        return None
    on_kernel = {o.name for o in run.data["report"].ops
                 if o.method_executed == METHOD[kernel]}
    seconds = (trace.kernel_s(run.trace) or {}).get(kernel)
    if not on_kernel or not seconds:
        return None
    pk = peaks.peak(run.device_kind)
    table = references.load(run.config).conv_table(run.config)
    layers = [counts.conv_counts(cv, run.data["nnz"][cv["name"]],
                                 run.data["batch"])
              for cv in table if cv["name"] in on_kernel]
    least = counts.least_seconds(layers, pk["flops_per_s"],
                                 pk["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / run.data["forwards"])
