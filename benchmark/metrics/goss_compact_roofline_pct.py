"""The sample's compaction against its roofline: the least bytes one
iteration's compaction moves (``_goss.compact_least_bytes``) at the chip's
HBM peak, times the iterations, over the seconds of region
``goss_compact``."""

from benchmark import peaks
from benchmark.metrics import _goss, _program, _regions


def read(ctx):
    sample, s = _goss.sample_rows(ctx), _regions.of(ctx, "goss_compact")
    if sample is None or not s:
        return None
    least = _goss.compact_least_bytes(ctx["rows"], ctx["cols"], sample) / peaks.peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    _program.say("goss_compact_roofline_pct", least_s=least, region_s=s, sample_rows=sample)
    return 100.0 * least * ctx["window"]["iterations"] / s
