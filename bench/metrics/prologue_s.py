"""Seconds per solve from the program's entry to its first chunk dispatch:
redundancy plan, norms and thresholds, state init (SolveReport.prologue_s,
the program's prologue span). None where the program reports no such
counter."""


def read(ctx):
    vals = [getattr(r, "prologue_s", None) for r in ctx.reports()]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
