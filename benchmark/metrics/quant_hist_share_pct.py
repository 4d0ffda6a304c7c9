"""Bucket builds' share of the device's busy time: the integer histogram
kernels and their reads of the integer row values / busy seconds, in percent
(``_quant.split_seconds``)."""

from benchmark.metrics import _quant


def read(ctx):
    return _quant.share_pct(ctx, "bucket", "quant_hist_share_pct")
