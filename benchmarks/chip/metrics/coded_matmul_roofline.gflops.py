"""Share of its roofline of the coded-matmul kernel, from the trace.

The kernel's device operations are found by these names: the Pallas call
inside the jitted ``coded_matmul_pallas``.  A change that renames the kernel
adds its new names here.
"""
from chipbench.layers import kernel_roofline

KERNEL_OPS = ("coded_matmul_pallas",)


def read(run):
    return kernel_roofline(run, KERNEL_OPS, "coded_matmul_roofline")
