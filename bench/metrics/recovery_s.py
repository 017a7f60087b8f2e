"""Seconds of recovery per solve that met a failure (SolveReport.recovery_s:
Alg. 2 reconstruction and scatter, or the checkpoint restore, timed by the
program up to a block on the device)."""


def read(ctx):
    reps = [r for r in ctx.reports() if r.events]
    if not reps:
        return None
    return sum(r.recovery_s for r in reps) / len(reps)
