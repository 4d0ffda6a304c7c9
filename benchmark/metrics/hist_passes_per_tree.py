"""Histogram passes over the rows per tree: the program's ``hist.passes``
counter over the window (every body, value dtype and scope; full trees, as the
merge's ledger counts) over its trees (one a boosting iteration: no cell is
multiclass).  A count: the same in every run."""

from benchmark.metrics import _regions


def read(ctx):
    passes, trees = _regions.counted(ctx, "hist.passes"), ctx["window"].get("iterations")
    return None if passes is None or not trees else passes / trees
