"""Device seconds of the windowed grower's per-slot row masks and moves
(region ``row_route``) per boosting iteration."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_iteration(ctx, "row_route")
