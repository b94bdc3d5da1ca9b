"""Roofline share of the ELL kernel (``sparse_conv``, VPU) over the layers
the plan runs on it; see ``perfbench/kernel_roofline.py``."""
from perfbench import kernel_roofline


def read(run):
    return kernel_roofline.share(run, "sparse_conv")
