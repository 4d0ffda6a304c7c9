"""The levels the window's fits rounded to: the hessian channel's largest
bucket, which is LightGBM's ``num_grad_quant_bins`` (gradients take half of it
a side, the count's bucket is 1), from the program's counter
``train.quant_levels{channel=...}`` (set once a fit) over the window / its
fits.  A count: the same in every run, and another number as soon as a change
rounds to other levels."""

from benchmark.metrics import _program, _quant


def read(ctx):
    lv = _quant.levels(ctx)
    if not lv or "hess" not in lv:
        return None
    _program.say("quant_levels", **lv)
    return lv["hess"]
