"""What the readers of the program's own spans and counters share.

Spans come from the program's reader in this process
(``mmlspark_tpu.obs.flight.spans()``: records ``name, start_ns, end_ns,
parent, parent_id, id, attrs`` on the program's ``monotonic_ns`` clock), or
from ``ctx["spans"]`` where a test hands a list in.  The traced window is one
fit and one evaluation, so the last ``booster.train`` and the last
``booster.score_binned`` are the window's.  A program without the reader, the
span or the counter gives ``None``, never an error and never 0.
"""

import sys


def spans(ctx):
    if "spans" in ctx:
        return ctx["spans"]
    try:
        from mmlspark_tpu.obs import flight
    except ImportError:
        return None
    reader = getattr(flight, "spans", None)
    return reader() if reader else None


def last_span(ctx, name):
    """The span of that name that began last, or ``None``."""
    found = [s for s in spans(ctx) or () if s["name"] == name]
    return max(found, key=lambda s: s["start_ns"]) if found else None


def seconds(span) -> float:
    return (span["end_ns"] - span["start_ns"]) / 1e9


def window_count(ctx, name):
    """A counter's rise over the window, ``None`` where the program has no
    such counter."""
    after = ctx["window_counters"].get(name)
    if after is None:
        return None
    return after - ctx["setup_counters"].get(name, 0.0)


def say(metric: str, **numbers) -> None:
    """The numbers a reader reports beside its value, on standard error."""
    print(f"{metric} " + " ".join(f"{k}={v:.6g}" for k, v in numbers.items() if v is not None), file=sys.stderr)
