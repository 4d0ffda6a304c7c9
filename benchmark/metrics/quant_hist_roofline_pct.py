"""Bucket builds' share of their roofline: the least time one tree's bucket
histograms need (``_quant.least_work``: every binned byte and every bucket
read once), times the trees traced, over the bucket builds' summed time."""

import sys

from benchmark import peaks
from benchmark.metrics import _quant


def read(ctx):
    s = _quant.split_seconds(ctx)
    if not s or s["bucket"] <= 0:
        return None
    work = _quant.least_work(ctx["rows"], ctx["cols"], _quant.value_bytes(ctx))
    least, binds = _quant.floor_seconds(work, peaks.peaks(ctx["device_kind"]))
    print(f"quant_hist_roofline_pct: least {least:.6f} s per tree, bound by {binds}", file=sys.stderr)
    return 100.0 * least * ctx["window"]["iterations"] / s["bucket"]
