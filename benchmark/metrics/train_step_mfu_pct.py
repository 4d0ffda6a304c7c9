"""The whole boosting iteration's share of the chip's peak: the least time
the iteration's unavoidable work needs, over the wall it took."""

import sys

from benchmark import peaks


def read(ctx):
    w = ctx["window"]
    least, binds = peaks.floor_seconds(peaks.step_least_work(ctx["rows"], ctx["cols"]), peaks.peaks(ctx["device_kind"]))
    print(f"train_step_mfu_pct: least {least:.6f} s per iteration, bound by {binds}", file=sys.stderr)
    return 100.0 * least / (w["wall_s"] / w["iterations"])
