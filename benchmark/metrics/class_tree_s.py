"""Device seconds of one class tree: the traced window's busy seconds (one
fit and its evaluation) over the trees the program counts it grew
(``train.class_trees``).  What growing the K trees of an iteration
together, rather than one after another, would move."""

from benchmark.metrics import _class


def read(ctx):
    trees, tr = _class.class_trees(ctx), ctx.get("trace")
    return None if trees is None or not tr else tr["busy_s"] / trees
