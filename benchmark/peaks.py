"""Peaks of the chips the benchmark knows, and the least work a boosting
iteration needs, from shapes alone.

The least-work functions count what the ALGORITHM cannot avoid, never what
today's implementation does (its number of histogram passes, its one-hot
FLOPs), so a share of the roofline reads the same work whatever implements
the step.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no peaks recorded for device kind {device_kind!r}; add it to benchmark/peaks.py with its source")
    return PEAKS[device_kind]


def hist_least_work(rows: int, cols: int) -> dict:
    """One tree's histograms: every binned byte and each row's gradient and
    hessian (float32) read once -- the root histogram, which no implementation
    avoids -- and two adds per row-column."""
    return {"bytes": rows * cols + rows * 8, "ops": 2 * rows * cols}


def step_least_work(rows: int, cols: int) -> dict:
    """One boosting iteration: the histograms, and each row's score read and
    written once (float32)."""
    w = hist_least_work(rows, cols)
    return {"bytes": w["bytes"] + rows * 8, "ops": w["ops"]}


def floor_seconds(work: dict, peak: dict) -> tuple:
    """``(least seconds, which bound binds)`` on one chip."""
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["ops"] / peak["bf16_flops_per_s"]
    return (by_bytes, "hbm_bytes") if by_bytes >= by_ops else (by_ops, "flops")
