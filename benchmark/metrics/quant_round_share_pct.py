"""The rounding's share of the device's busy time: the draw and the buckets
of every row value, once a tree / busy seconds, in percent
(``_quant.split_seconds``; the scales' two reductions are not found and not
counted)."""

from benchmark.metrics import _program, _quant


def read(ctx):
    s = _quant.split_seconds(ctx)
    if not s or s["round"] <= 0:
        return None
    _program.say("quant_round_share_pct", round_s=s["round"], busy_s=ctx["trace"]["busy_s"])
    return 100.0 * s["round"] / ctx["trace"]["busy_s"]
