"""Share of the training matrix a histogram pass reads: the program's
``hist.rowcols`` over the window, over its ``hist.passes`` times the rows
and columns of the training set.  1.0 where every pass reads every row; a
GOSS fit that builds its histograms over the sample alone reads its share of
the rows (rounded up to whole histogram chunks)."""

from benchmark.metrics import _regions


def read(ctx):
    rowcols, passes = _regions.counted(ctx, "hist.rowcols"), _regions.counted(ctx, "hist.passes")
    return None if not rowcols or not passes else rowcols / (passes * ctx["rows"] * ctx["cols"])
