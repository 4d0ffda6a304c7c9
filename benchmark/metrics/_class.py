"""What the readers of a fit of K > 1 trees an iteration share: the
program's count of them.

The program counts ``train.class_trees`` (K trees an iteration, at every
dispatch of a multiclass fit) and names the device regions ``class_grad``
(the objective's ``(K, n)`` gradient) and ``class_update`` (the K-row score
update).  A program that counts no class trees (a fit of one tree an
iteration, or one from before the counter) gives ``None`` to every reader,
never 0.
"""

from benchmark.metrics import _program, _regions


def class_trees(ctx):
    """Trees the window's fits grew, ``None`` where the program counts none."""
    return _program.window_count(ctx, "train.class_trees") or None


def region_share(ctx, region):
    """Seconds of ``region`` over the busy seconds, in %."""
    if class_trees(ctx) is None:
        return None
    s = _regions.of(ctx, region)
    return None if s is None else 100.0 * s / ctx["trace"]["busy_s"]
