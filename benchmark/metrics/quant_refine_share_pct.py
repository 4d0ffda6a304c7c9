"""The float32 refinement's share of the device's busy time: the float
histogram kernels of a quantized fit (each pass's winner columns
re-accumulated from the float32 rows) and their reads / busy seconds, in
percent (``_quant.split_seconds``)."""

from benchmark.metrics import _program, _quant


def read(ctx):
    s = _quant.split_seconds(ctx)
    if not s or s["refine"] <= 0:
        return None
    _program.say("quant_refine_share_pct", refine_s=s["refine"], busy_s=ctx["trace"]["busy_s"])
    return 100.0 * s["refine"] / ctx["trace"]["busy_s"]
