"""Share of the window's wall spent evaluating each new model on the resident
holdout (scoring and the loss, to the device's answer), by the host's clock
over all the window's evaluations together."""


def read(ctx):
    w = ctx["window"]
    if not w.get("eval_s"):
        return None
    return 100.0 * sum(w["eval_s"]) / w["wall_s"]
