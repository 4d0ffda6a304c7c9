"""Share of the device's busy time that a GOSS fit spends sampling: the
regions ``goss_select`` + ``goss_compact`` + ``goss_route`` over the busy
seconds."""

from benchmark.metrics import _goss, _regions


def read(ctx):
    if _goss.sample_rows(ctx) is None:
        return None
    s = _regions.of(ctx, *_goss.REGIONS)
    return None if s is None else 100.0 * s / ctx["trace"]["busy_s"]
