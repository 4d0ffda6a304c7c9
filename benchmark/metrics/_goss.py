"""What the readers of a GOSS fit share: its regions and its sample.

The program counts each iteration's sample at every dispatch
(``goss.top_rows``, ``goss.rest_rows``, ``goss.sample_rows``) and names the
device regions of the sampling ``goss_select`` (rank and draw),
``goss_compact`` (the sample gathered into a buffer of its own) and
``goss_route`` (every row routed through the new tree).  A program that
counts no sample (a fit without GOSS, or one from before these counters)
gives ``None`` to every reader that needs the sample, never 0.
"""

from benchmark.metrics import _program

REGIONS = ("goss_select", "goss_compact", "goss_route")


def sample_rows(ctx):
    """Rows of one iteration's sample, over the window; ``None`` where the
    program counts none."""
    rows, iters = _program.window_count(ctx, "goss.sample_rows"), ctx["window"].get("iterations")
    return None if not rows or not iters else rows / iters


def compact_least_bytes(rows: int, cols: int, sample: float) -> float:
    """The least bytes of one iteration's compaction: every binned byte read
    once to find the sample's rows (``cols`` a row), the sample's bytes
    written once, and its three float32 row values (12 bytes a row)."""
    return rows * cols + sample * cols + 12 * sample
