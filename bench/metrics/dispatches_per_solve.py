"""Chunk dispatches of the driver's host loop per solve
(SolveReport.run_calls)."""


def read(ctx):
    reps = ctx.reports()
    if not reps:
        return None
    return sum(r.run_calls for r in reps) / len(reps)
