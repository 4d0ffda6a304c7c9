"""Collectives' share of the device's busy time: the summed time of the
``all-reduce`` / ``reduce-scatter`` / ``all-gather`` / ``collective-permute``
ops (the histogram merge, the winner exchange, the leaf totals) over busy."""

from benchmark.metrics import _program
from benchmark.metrics._merge import collective_seconds


def read(ctx):
    s = collective_seconds(ctx)
    if s is None:
        return None
    _program.say("hist_merge_share_pct", collective_s=s, busy_s=ctx["trace"]["busy_s"])
    return 100.0 * s / ctx["trace"]["busy_s"]
