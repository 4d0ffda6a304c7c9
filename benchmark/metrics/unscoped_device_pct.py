"""Share of the device's busy seconds under no region of the program's
registry: ops with no scope, ops the program's maps do not hold, and keys two
programs put in different regions."""

from benchmark.metrics import _regions


def read(ctx):
    s = _regions.of(ctx, "unscoped")
    return None if s is None else 100.0 * s / ctx["trace"]["busy_s"]
