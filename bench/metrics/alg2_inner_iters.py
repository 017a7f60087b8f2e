"""Iterations of Alg. 2's line-8 inner solve (A_ff x_f = w), averaged over
the fail-stop events of the window (EventReport.inner_iters). None where
the program reports no such counter."""


def read(ctx):
    vals = [getattr(e, "inner_iters", None) for r in ctx.reports()
            for e in r.events if e.kind == "fail-stop"]
    if not vals or None in vals:
        return None
    return sum(vals) / len(vals)
