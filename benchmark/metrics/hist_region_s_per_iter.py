"""Device seconds under the histogram scopes (``hist_build``, ``quant_hist``,
``quant_refine``: the kernels, and what the compiler put beside them, the
un-factoring reshape and the like) per boosting iteration."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_iteration(ctx, *_regions.HIST)
