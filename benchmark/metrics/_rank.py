"""What the ranking readers share: the plan's bucket shapes, from the
program's ``booster.rank_plan`` span."""

from benchmark.metrics import _program


def plan_span(ctx):
    """The traced fit's ``booster.rank_plan`` span, ``None`` where the program
    has none (a parent from before the ranking cell)."""
    return _program.last_span(ctx, "booster.rank_plan")


def bucket_shapes(span):
    """``[(G_b, M_b), ...]`` of the training plan, from the span's ``shapes``."""
    shapes = (span or {}).get("attrs", {}).get("shapes")
    if not shapes:
        return None
    return [tuple(int(v) for v in s.split("x")) for s in shapes.split()]
