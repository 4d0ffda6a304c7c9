"""Seconds of Python tracing inside the window, where every shape is warm and
none should be left: the program's ``jit.trace_s`` counter (jax's own
trace-duration events) over the window.  ``jit.traces`` and
``predict.scorer_builds`` go beside it on standard error: counts, the same in
every run of one seed."""

from benchmark.metrics import _program


def read(ctx):
    trace_s = _program.window_count(ctx, "jit.trace_s")
    if trace_s is None:
        return None
    _program.say(
        "window_retrace_s",
        **{name: _program.window_count(ctx, name) for name in ("jit.traces", "predict.scorer_builds", "jit.lower_s", "jit.backend_s")},
    )
    return trace_s
