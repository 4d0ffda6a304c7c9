"""Device seconds of the histogram chunk loop's slices and pads (region
``chunk_copy``) per boosting iteration."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_iteration(ctx, "chunk_copy")
