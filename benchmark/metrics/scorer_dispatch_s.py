"""Host seconds of the traced evaluation's scorer call: the program's
``booster.score_binned`` span.  The call returns at dispatch, so this is what
the host does before the device can start (a scorer built, traced, lowered,
loaded and enqueued), not the scoring."""

from benchmark.metrics import _program


def read(ctx):
    call = _program.last_span(ctx, "booster.score_binned")
    if call is None:
        return None
    _program.say("scorer_dispatch_s", built=float(bool(call["attrs"].get("built"))))
    return _program.seconds(call)
