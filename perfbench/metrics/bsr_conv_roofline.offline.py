"""Roofline share of the BCSR kernel (``bsr_conv``, MXU) over the layers
the plan runs on it; see ``perfbench/kernel_roofline.py``."""
from perfbench import kernel_roofline


def read(run):
    return kernel_roofline.share(run, "bsr_conv")
