"""Seconds from a fail-stop to the end of the iteration that resumes from
its recovery, averaged over the fail-stop events of the window
(EventReport.event_s: the program's event span through its resume step,
which ends on a read-back of the residual norm, so on the device). None
where the program reports no such counter."""


def read(ctx):
    vals = [getattr(e, "event_s", None) for r in ctx.reports()
            for e in r.events if e.kind == "fail-stop"]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
