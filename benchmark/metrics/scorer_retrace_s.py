"""Seconds of Python tracing under the scorer's call per evaluation of the
window: the program's ``jit.trace_s{span=booster.score_binned}`` (jax's trace
durations by the innermost open obs span).  What was traced under any other
span goes beside it on standard error."""

from benchmark.metrics import _program

COUNTER = "jit.trace_s{span="


def read(ctx):
    by_span = {k[len(COUNTER):-1]: _program.window_count(ctx, k) for k in ctx["window_counters"] if k.startswith(COUNTER)}
    evals = len(ctx["window"].get("eval_s") or ())
    if "booster.score_binned" not in by_span or not evals:
        return None
    _program.say("scorer_retrace_s", **{k: v for k, v in sorted(by_span.items()) if v})
    return by_span["booster.score_binned"] / evals
