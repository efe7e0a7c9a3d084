"""Kernels: the Pallas decode attention's share of its roofline, in %."""
from bench.reduce import kernel_roofline


def read(run):
    return kernel_roofline(run, "decode_attention")
