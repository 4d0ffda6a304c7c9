"""Device busy seconds outside the histogram kernels, per boosting iteration:
split scan, leaf statistics, leaf delta and score update."""

from benchmark.metrics._hist import kernel_seconds


def read(ctx):
    s = kernel_seconds(ctx)
    if s is None:
        return None
    return (ctx["trace"]["busy_s"] - s) / ctx["window"]["iterations"]
