"""What the readers of the device's seconds BY REGION share: the trace's
seconds by op (``ctx["trace"]["op_s"]``, keys ``"<instruction> <shape>"``)
joined with the program's own map of what it compiled
(``mmlspark_tpu.obs.device.regions()``: for each program dispatched under obs,
``{(instruction, shape): region}``, the region being the innermost
``jax.named_scope`` of the program's registry on the instruction's path; a
test hands a map in as ``ctx["regions"]``).  The program lowers and loads its
executables when asked, here, after the window.  A program without the map
gives ``None`` for every region metric, and so does a map that finds under 90 %
of the busy seconds' instructions (of the seconds summed op by op): the coverage is printed, never a guess.

Also the sums of the program's labelled ``hist.*`` counters over the window.
"""

from benchmark.metrics import _program

HIST = ("hist_build", "quant_hist", "quant_refine")
COVERAGE = 0.9


def _maps(ctx):
    if "regions" in ctx:
        return ctx["regions"]
    try:
        from mmlspark_tpu.obs import device
    except ImportError:
        return None
    reader = getattr(device, "regions", None)
    return reader() if reader else None


def seconds(ctx):
    """``{region: seconds}`` over the traced window, with ``unscoped``: ops
    with no region, ops no program's map holds, and ops whose key two
    programs put in different regions.  Made once a run and printed whole."""
    if "_region_s" not in ctx:
        ctx["_region_s"] = _join(ctx)
    return ctx["_region_s"]


def _join(ctx):
    tr, maps = ctx.get("trace"), _maps(ctx)
    if not tr or not maps:
        return None
    out, found = {"unscoped": 0.0}, 0.0
    for op, s in tr["op_s"].items():
        key = tuple(op.split(" ", 1))
        held = {m[key] for m in maps.values() if key in m}
        found += s if held else 0.0
        region = held.pop() if len(held) == 1 else None
        out[region or "unscoped"] = out.get(region or "unscoped", 0.0) + s
    total = sum(tr["op_s"].values())  # the busy seconds, op by op
    _program.say("regions", coverage=found / total, op_s=total, busy_s=tr["busy_s"], **dict(sorted(out.items())))
    return out if found >= COVERAGE * total else None


def of(ctx, *regions):
    """Summed seconds of ``regions``, ``None`` where the table is."""
    table = seconds(ctx)
    return None if table is None else sum(table.get(r, 0.0) for r in regions)


def per_iteration(ctx, *regions):
    s, iters = of(ctx, *regions), ctx["window"].get("iterations")
    return None if s is None or not iters else s / iters


def counted(ctx, name):
    """A labelled counter's rise over the window, summed over its labels;
    ``None`` where the program has no such counter."""
    keys = [k for k in ctx["window_counters"] if k.startswith(name + "{")]
    return sum(_program.window_count(ctx, k) for k in keys) if keys else None


def per_rowcol(ctx, name):
    work, rowcols = counted(ctx, name), counted(ctx, "hist.rowcols")
    return None if work is None or not rowcols else work / rowcols
