"""Nanoseconds of the histogram regions per row and padded column the
kernels read: the regions' seconds over the program's ``hist.rowcols`` counter
(a chip's rows x padded columns, every pass and chunk of the window)."""

from benchmark.metrics import _regions


def read(ctx):
    s, rowcols = _regions.of(ctx, *_regions.HIST), _regions.counted(ctx, "hist.rowcols")
    return None if s is None or not rowcols else 1e9 * s / rowcols
