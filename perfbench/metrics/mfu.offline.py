"""Share of the chip's bf16 peak that the offline window reached, counting
only the operations a forward requires (``perfbench.counts``: nonzero
weights of pruned convs, all weights of dense convs and the FC layers):
forwards completed in the window times their operations, over the window's
host-clock seconds times the peak, in percent.  Counted so, it cannot pass
100% whatever method runs a layer."""
from perfbench import peaks


def read(run):
    if not run.data.get("forwards"):
        return None
    peak = peaks.peak(run.device_kind)["flops_per_s"]
    flops = run.data["forwards"] * run.data["flops_per_forward"]
    return 100.0 * flops / (run.window_s * peak)
