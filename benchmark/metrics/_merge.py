"""What the readers of the histogram merge share: the collectives' names in
the device trace, the interconnect's peak, and the least bytes a merge moves.

``peaks.py`` holds the chips' compute and memory peaks; the interconnect's
lives here with the one layer that reads it.
"""

from benchmark.trace import seconds_of

# The collective ops as the device trace names them.  The trace names an op by
# its HLO instruction, and JAX names a collective's instruction after the
# primitive that made it: the merge is ``reduce_scatter.27 f32[3,8,10,256]``,
# the leaf totals and the owner's broadcast ``psum.54`` / ``psum.55``, the
# winner exchange ``all-gather.9 f32[4,5,63]``, the log loss's mean
# ``all-reduce f32[]`` (PR 27's traces).  Both spellings, so that a renamed
# instruction is still found by its opcode's.
COLLECTIVES = (
    "all-reduce", "reduce-scatter", "all-gather", "collective-permute", "all-to-all",
    "psum", "reduce_scatter", "all_gather", "ppermute", "all_to_all", "pmax", "pmin",
)

ICI_BYTES_PER_S = {
    # Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of interchip interconnect a chip
    "TPU v5 lite": 1600e9 / 8,
}


def collective_seconds(ctx):
    """Summed device seconds of the collective ops (a chip's mean), ``None``
    where the trace holds none."""
    tr = ctx.get("trace")
    if not tr:
        return None
    s = seconds_of(tr["op_s"], COLLECTIVES)
    return s if s > 0 else None


def merge_least_bytes(cols: int, bins: int, leaves: int, chips: int) -> float:
    """The least bytes a chip must receive to merge ONE tree's histograms,
    from shapes alone, whatever merge implements it: for each of the tree's
    ``leaves - 1`` splits one gradient and one hessian histogram of ``cols x
    bins`` float32 (its sibling comes by subtraction), of which the chip
    already holds its own ``1 / chips``."""
    return 2 * cols * bins * 4 * (leaves - 1) * (chips - 1) / chips
