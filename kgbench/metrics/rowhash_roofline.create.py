"""Kernels: the rowhash and hash_neighbor_flags Pallas kernels' share of
their HBM roofline, %: the least time their operand and result bytes take
at the chip's published bandwidth over their summed device time."""
from kgbench.layers import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, {"rowhash", "hash_neighbor_flags"})
