"""The float32 refinement's share of the device's busy time: the float
histogram kernels of a quantized fit (each pass's winner columns
re-accumulated from the float32 rows), the composed column and their reads /
busy seconds, in percent (``_quant.split_seconds``)."""

from benchmark.metrics import _quant


def read(ctx):
    return _quant.share_pct(ctx, "refine", "quant_refine_share_pct")
