"""What the plan's buckets pay in padding: 100 x (1 - pair terms that are real
/ pair terms formed) over the window, from the counters ``rank.pair_terms``
(sum over queries of min(K, M_q) M_q) and ``rank.pair_slots`` (sum over buckets
of G_b min(K, M_b) M_b), each counted at every dispatch x its iterations."""

from benchmark.metrics import _program


def read(ctx):
    terms = _program.window_count(ctx, "rank.pair_terms")
    slots = _program.window_count(ctx, "rank.pair_slots")
    if not terms or not slots:
        return None
    _program.say("rank_pad_waste_pct", pair_terms=terms, pair_slots=slots, queries=_program.window_count(ctx, "rank.queries"))
    return 100.0 * (1.0 - terms / slots)
