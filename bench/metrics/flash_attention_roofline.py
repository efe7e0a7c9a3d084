"""Kernels: the Pallas flash attention's share of its roofline, in %."""
from bench.reduce import kernel_roofline


def read(run):
    return kernel_roofline(run, "flash_attention")
