"""Host seconds of the traced fit's ``booster.rank_plan`` span: the query plan
looked up (near 0: it is kept with the resident data set) or, on a miss, built
from the group sizes and sent."""

from benchmark.metrics import _program, _rank


def read(ctx):
    span = _rank.plan_span(ctx)
    if span is None:
        return None
    _program.say("rank_plan_s", cache_hit=float(bool(span.get("attrs", {}).get("cache_hit"))))
    return _program.seconds(span)
