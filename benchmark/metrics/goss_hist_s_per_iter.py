"""Device seconds of a GOSS fit's histograms per boosting iteration: the
histogram regions (``_regions.HIST``), or, where the program has no region
map, the histogram kernels' own seconds (``_hist``)."""

from benchmark.metrics import _hist, _regions


def read(ctx):
    s = _regions.per_iteration(ctx, *_regions.HIST)
    if s is not None:
        return s
    k, iters = _hist.kernel_seconds(ctx), ctx["window"].get("iterations")
    return None if k is None or not iters else k / iters
