"""Programs compiled or re-traced during set-up: the program's own
``jit_cache.miss`` and ``trace_cache.miss`` counters.  0 on a warm checkout."""


def read(ctx):
    c = ctx["setup_counters"]
    return float(c.get("jit_cache.miss", 0.0) + c.get("trace_cache.miss", 0.0))
