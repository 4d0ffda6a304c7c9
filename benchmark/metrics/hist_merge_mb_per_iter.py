"""Megabytes each chip receives in one boosting iteration's collectives: the
program's executed counter ``train.merge_bytes{op=...}`` (added at each
``booster.scan_dispatch`` from the cached program's own ledger, so a window of
cached fits counts what ran) over the window, an iteration.  A count: the
same in every run."""

from benchmark.metrics import _program

COUNTER = "train.merge_bytes"


def read(ctx):
    ops = [k for k in ctx["window_counters"] if k == COUNTER or k.startswith(COUNTER + "{")]
    iters = ctx["window"].get("iterations")
    if not ops or not iters:
        return None
    by_op = {(k[len(COUNTER):].strip("{}").replace("op=", "") or "all") + "_bytes": _program.window_count(ctx, k) for k in ops}
    _program.say("hist_merge_mb_per_iter", **by_op)
    return sum(by_op.values()) / iters / 1e6
