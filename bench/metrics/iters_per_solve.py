"""Iterations executed per solve: converged iteration plus the iterations
re-done after each rollback (SolveReport.converged_iter + wasted_iters)."""


def read(ctx):
    reps = ctx.reports()
    if not reps:
        return None
    return sum(r.converged_iter + r.wasted_iters for r in reps) / len(reps)
