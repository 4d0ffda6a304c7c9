"""Bucket builds' share of the device's busy time: the integer histogram
kernels and their reads of the integer row values / busy seconds, in percent
(``_quant.split_seconds``)."""

from benchmark.metrics import _program, _quant


def read(ctx):
    s = _quant.split_seconds(ctx)
    if not s or s["bucket"] <= 0:
        return None
    _program.say("quant_hist_share_pct", bucket_s=s["bucket"], busy_s=ctx["trace"]["busy_s"])
    return 100.0 * s["bucket"] / ctx["trace"]["busy_s"]
