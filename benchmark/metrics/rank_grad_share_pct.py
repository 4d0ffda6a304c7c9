"""Share of the device's busy time in the ranking gradient and the by-query
NDCG: seconds of their ops / busy seconds, in percent.

A reader gets seconds by op name only, and an op's name there is its HLO name
and the shape it produces (``fusion.9 f32[10493,20,512]``), so the ranking ops
are found BY SHAPE: the gradient and the metric work on ``(G_b, M_b)`` matrices
a bucket of the query plan, on ``(G_b, K, M_b)`` pair terms and ``(G_b, K)``
top rows, on the buckets' flat layout and on row vectors lengthened by the
widest bucket, and nothing else in a fit or a scorer has those shapes.  The
training plan's shapes come from the program's ``booster.rank_plan`` span
(``shapes``, ``rows``), the evaluation's from the traffic
(``window["eval_plan"]``).  The slices that lay a bucket's queries side by
side run as one ``dynamic-slice... f32[M_b]`` a query: those are counted by
that name and width.  Not found this way, so not counted: ops of a per-query
scalar ``(G_b,)`` and copies of a plain row vector (a few milliseconds a fit);
the share reads a little low, never high.
"""

import re

from benchmark.metrics import _program, _rank


def plan_shapes(buckets, rows: int, k: int) -> set:
    """Dimension tuples (leading 1s and 2s stripped) that only ranking ops
    have, for a plan of ``buckets`` = ``[(G_b, M_b), ...]`` over ``rows``."""
    out = {(rows + max(m for _, m in buckets),), (sum(g * m for g, m in buckets) + 1,), (rows, 2), (2, rows)}
    for g, m in buckets:
        if g < 8:
            continue  # a handful of queries: no time, and (1, M) is anyone's shape
        kb = min(k, m)
        out |= {(g, m), (g, kb, m), (g, kb), (g * m,), ("dynamic-slice", m)}
    return out


def is_ranking_op(name: str, shapes: set) -> bool:
    m = re.search(r"\[([0-9,]+)\]", name)
    if not m:
        return False
    dims = [int(d) for d in m.group(1).split(",")]
    if tuple(dims) in shapes:
        return True
    while len(dims) > 1 and dims[0] in (1, 2):
        dims = dims[1:]
    return tuple(dims) in shapes or (name.startswith("dynamic-slice") and ("dynamic-slice", *dims) in shapes)


def read(ctx):
    tr = ctx.get("trace")
    span = _rank.plan_span(ctx)
    buckets = _rank.bucket_shapes(span)
    if not tr or not buckets:
        return None
    params = ctx["cfg"]["params"]
    shapes = plan_shapes(buckets, int(span["attrs"]["rows"]), int(params.get("max_position", 20)))
    ev = ctx["window"].get("eval_plan")
    if ev:
        shapes |= plan_shapes([tuple(b) for b in ev["buckets"]], int(ev["rows"]), int(ev["k"]))
    s = sum(d for name, d in tr["op_s"].items() if is_ranking_op(name, shapes))
    if s <= 0:
        return None
    _program.say("rank_grad_share_pct", ranking_s=s, busy_s=tr["busy_s"])
    return 100.0 * s / tr["busy_s"]
