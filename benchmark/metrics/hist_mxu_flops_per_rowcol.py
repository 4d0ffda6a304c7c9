"""MXU flops the histogram bodies issue per row and padded column read
(2 M N of the body's own matmul): ``hist.mxu_flops`` over ``hist.rowcols``.
A count: the same in every run, and another as soon as a body's tile changes."""

from benchmark.metrics import _regions


def read(ctx):
    return _regions.per_rowcol(ctx, "hist.mxu_flops")
