"""Histogram kernels' share of the device's busy time."""

from benchmark.metrics._hist import kernel_seconds


def read(ctx):
    s = kernel_seconds(ctx)
    return None if s is None else 100.0 * s / ctx["trace"]["busy_s"]
