"""Seconds of set-up spent in the backend's compile step: the program's
``jit.backend_s`` counter (jax's backend-compile durations: an executable
compiled, or loaded from the persistent cache) over set-up.  Tracing and
lowering over set-up go beside it on standard error."""

from benchmark.metrics import _program


def read(ctx):
    setup = ctx["setup_counters"]
    if "jit.backend_s" not in setup:
        return None
    _program.say("setup_program_load_s", **{name: setup.get(name) for name in ("jit.trace_s", "jit.lower_s", "jit.traces")})
    return setup["jit.backend_s"]
