"""The rounding's share of the device's busy time: the row values stacked,
drawn over and rounded, once a tree / busy seconds, in percent
(``_quant.split_seconds``; the scales' two reductions are not found and not
counted)."""

from benchmark.metrics import _quant


def read(ctx):
    return _quant.share_pct(ctx, "round", "quant_round_share_pct")
