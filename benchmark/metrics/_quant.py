"""What the quantized-training readers share: whether the window's fits were
quantized, the seconds of their bucket builds, of the float32 refinement and
of the rounding, and the least work a tree's bucket histograms need.

A reader gets seconds by op name only, and a name there is the op's HLO name
and the shape it produces (``fusion.9 s32[1,39845888]``; of an op with several
results the first, ``pad_maximum_fusion.6 (f32[3,39845888]``).  The three
parts are told apart by what they PRODUCE, which follows the arithmetic and
not the implementation:

- a bucket build is a histogram kernel (``_hist.KERNELS``: any kernel of
  ``ops/pallas_hist.py``, whatever its body) whose result is integer
  (``s32[...]``); with it count the chunk loop's slices of the integer row
  values (``s16[3,chunk]`` or ``s8[3,chunk]``: only bucket builds read them);
- the refinement is every histogram kernel with a float result in a fit that
  is quantized (every full pass of such a fit is a bucket build, so the float
  kernels that are left are the winners' columns), with what only it makes
  and reads: the winners' columns composed into one (``s32[1,rows]``), its
  chunks and their padding (``s32[k,chunk]``: the bins themselves are ``u8``)
  and the chunks of the float32 row values (``f32[3,chunk]``);
- the rounding is whatever produces an array of the row values' shape
  ``[3,rows]``: on the chip one fusion stacks the three channels, draws and
  rounds (``(f32[3,rows], s16[3,rows])``).  Not found this way, so not
  counted: the two reductions of the scales (scalars, 3 ms a fit).

The row masks, the leaf delta and the chunk loop's copies of the bins are the
float fit's too and belong to none of the three.

Whether a fit was quantized is read from the program's counter
``train.quant_levels``; a program without it gives ``None`` everywhere.
"""

import re

from benchmark.metrics import _hist, _program

LEVELS = "train.quant_levels"
_SHAPE = re.compile(r"[ (]([a-z]+[0-9]*)\[([0-9,]*)\]")


def levels(ctx):
    """``{channel: largest bucket}`` of the window's fits, from the counter's
    rise over the window / the fits; ``None`` where no fit was quantized."""
    fits = ctx["window"].get("attempted")
    out = {}
    for k in ctx["window_counters"]:
        if k.startswith(LEVELS + "{"):
            rise = _program.window_count(ctx, k)
            if rise and fits:
                out[k[len(LEVELS):].strip("{}").replace("channel=", "")] = rise / fits
    return out or None


def _produces(name: str):
    """``(dtype, dims)`` of the shape an op's name says it produces."""
    m = _SHAPE.search(name)
    return (m.group(1), tuple(int(d) for d in m.group(2).split(",") if d)) if m else (None, ())


def _is_kernel(name: str) -> bool:
    return any(k in name for k in _hist.KERNELS)


def _integer(dtype) -> bool:
    return dtype is not None and dtype[0] in "su" and dtype != "u32"


def split_seconds(ctx):
    """``{"bucket", "refine", "round"}`` seconds of the traced window, ``None``
    where there is no trace or no quantized fit in it."""
    tr = ctx.get("trace")
    if not tr or not levels(ctx):
        return None
    rows, chunk = int(ctx["rows"]), int(ctx["cfg"]["chunk_rows"])
    out = {"bucket": 0.0, "refine": 0.0, "round": 0.0}
    for name, s in tr["op_s"].items():
        dtype, dims = _produces(name)
        if _is_kernel(name):
            out["bucket" if dtype == "s32" else "refine"] += s
        elif dims == (3, chunk) and _integer(dtype):
            out["bucket"] += s
        elif dims == (3, chunk) or (dtype == "s32" and (dims == (1, rows) or (len(dims) == 2 and dims[1] == chunk))):
            out["refine"] += s
        elif dims == (3, rows):
            out["round"] += s
    return out


def share_pct(ctx, part: str, metric: str):
    """One part's seconds / busy seconds, in percent; ``None`` where the part
    has nothing in the trace."""
    s = split_seconds(ctx)
    if not s or s[part] <= 0:
        return None
    busy = ctx["trace"]["busy_s"]
    _program.say(metric, **{part + "_s": s[part], "busy_s": busy})
    return 100.0 * s[part] / busy


def value_bytes(ctx) -> int:
    """Bytes of one bucket as the program keeps it: the width of the integer
    row values the bucket builds read, chunk by chunk, in the trace (``s8``
    1, else ``s16``'s 2)."""
    chunk = int(ctx["cfg"]["chunk_rows"])
    for name in ctx["trace"]["op_s"]:
        if _produces(name) == ("s8", (3, chunk)):
            return 1
    return 2


def least_work(rows: int, cols: int, bucket_bytes: int) -> dict:
    """One tree's bucket histograms: every binned byte and each row's three
    buckets (gradient, hessian, count, at the bucket's width) read once --
    the root histogram, which no implementation avoids -- and two integer adds
    a row-column, counted as ``peaks.hist_least_work`` counts the float one."""
    return {"bytes": rows * cols + rows * 3 * bucket_bytes, "ops": 2 * rows * cols}


def floor_seconds(work: dict, peak: dict) -> tuple:
    """``(least seconds, which bound binds)``: bytes over the HBM's rate, or
    integer operations over the chip's int8 rate."""
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["ops"] / peak["int8_ops_per_s"]
    return (by_bytes, "hbm_bytes") if by_bytes >= by_ops else (by_ops, "int8_ops")
