"""Device seconds of the split scan, the leaf statistics and the leaf delta
(regions ``split_scan``, ``leaf_stats``, ``leaf_delta``) per boosting iteration."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_iteration(ctx, "split_scan", "leaf_stats", "leaf_delta")
