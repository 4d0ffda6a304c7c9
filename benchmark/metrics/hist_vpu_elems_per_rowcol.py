"""VPU element operations the histogram bodies form per row and padded
column read (every element of every elementwise array a body builds, as each
states beside its kernel): ``hist.vpu_elems`` over ``hist.rowcols``.  A count."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_rowcol(ctx, "hist.vpu_elems")
