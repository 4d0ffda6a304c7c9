"""Histogram kernels' share of their roofline: the least time one tree's
histograms need, times the trees traced, over the kernels' summed time."""

import sys

from benchmark import peaks
from benchmark.metrics._hist import kernel_seconds


def read(ctx):
    s = kernel_seconds(ctx)
    if s is None:
        return None
    least, binds = peaks.floor_seconds(peaks.hist_least_work(ctx["rows"], ctx["cols"]), peaks.peaks(ctx["device_kind"]))
    print(f"hist_kernel_roofline_pct: least {least:.6f} s per tree, bound by {binds}", file=sys.stderr)
    return 100.0 * least * ctx["window"]["iterations"] / s
