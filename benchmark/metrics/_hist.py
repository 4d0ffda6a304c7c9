"""What the histogram-kernel readers share: the kernels' names in the trace."""

from benchmark.trace import seconds_of

# The Pallas kernels of mmlspark_tpu/ops/pallas_hist.py.  The trace names a
# kernel's custom call after the jitted wrapper that holds it (_pallas_hist,
# _pallas_hist_by_leaf, _pallas_hist_by_leaf_nibble and their _int twins),
# not after the kernel body (_hist_kernel, ...): one prefix covers them all.
KERNELS = ("_pallas_hist",)


def kernel_seconds(ctx):
    tr = ctx.get("trace")
    if not tr:
        return None
    s = seconds_of(tr["op_s"], KERNELS)
    return s if s > 0 else None
