"""Host seconds of the traced fit before its one dispatch: the program's
``booster.prepare`` + ``booster.upload`` + ``booster.program`` spans, children
of the window's ``booster.train`` (configuration and padding; the row vectors
sent to the device; key schedules and the scan program's lookup).  The device
idles through all of it."""

from benchmark.metrics import _program

PHASES = ("booster.prepare", "booster.upload", "booster.program")


def read(ctx):
    fit = _program.last_span(ctx, "booster.train")
    if fit is None:
        return None
    by_phase = {
        s["name"]: _program.seconds(s)
        for s in _program.spans(ctx)
        if s.get("parent_id") == fit["id"] and s["name"] in PHASES
    }
    if not by_phase:
        return None
    _program.say("fit_host_lead_s", **{p.split(".")[1] + "_s": by_phase.get(p) for p in PHASES})
    return sum(by_phase.values())
