"""Megabytes a fit sends from the host to the device: the program's
``train.upload_bytes`` counter (every host array ``booster.upload`` sends,
counted at the send) over the window, a fit.  A count: the same in every run
of one seed."""

from benchmark.metrics import _program


def read(ctx):
    sent = _program.window_count(ctx, "train.upload_bytes")
    fits = ctx["window"].get("attempted")
    if sent is None or not fits:
        return None
    return sent / fits / 1e6
