"""Median ``adlb.solve`` (pack to extract) in the traced window; beside
``solve_kernel_ms`` it is the host's overhead on a device solve."""

from benchmarks.reduce import hostspans


def read(run):
    return hostspans.median_ms(run, "adlb.solve")
