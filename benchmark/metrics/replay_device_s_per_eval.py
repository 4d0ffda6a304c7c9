"""Device seconds of the replay scorer's steps (region ``replay_step``) per
evaluation of the window."""

from benchmark.metrics import _regions


def read(ctx):
    s, evals = _regions.of(ctx, "replay_step"), len(ctx["window"].get("eval_s") or ())
    return None if s is None or not evals else s / evals
