"""End-to-end driver: the paper's experiment (§5) on a generated problem.

Runs the full resilient-solver matrix — reference, ESR (T=1), ESRP, IMCR —
with worst-case failure injection (2 iterations before the storage stage
containing iteration C/2), prints the Table-2-style overhead report, and
finishes with a staggered multi-event scenario (failure → recover → fail
again, φ nodes simultaneously in the first event) with the per-event
recovery breakdown.

With ``--sdc`` an extra section injects silent data corruption (a bit flip
in the search direction p, and a perturbed redundancy-queue copy) instead
of a fail-stop: the invariant checks detect the corruption within one
check period, route it through the same Alg. 2 reconstruction, and the run
rejoins the clean trajectory — the report prints which detector fired, the
detection latency, and the distance to the corruption-free solution.

    PYTHONPATH=src python examples/solve_poisson_resilient.py \
        --kind poisson3d --nx 32 --nodes 16 --T 20 --phi 3 --precond ssor \
        --sdc
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np

from repro.core.driver import solve_resilient
from repro.core.failures import FailureEvent, SDCEvent
from repro.launch.compile_cache import use_compile_cache
from repro.sparse.matrices import build_problem


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="poisson3d",
                    choices=["poisson2d", "poisson3d", "banded"])
    ap.add_argument("--nx", type=int, default=32)
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--T", type=int, default=20)
    ap.add_argument("--phi", type=int, default=3)
    ap.add_argument("--rtol", type=float, default=1e-8)
    ap.add_argument("--precond", default="jacobi",
                    choices=["jacobi", "ssor", "chebyshev", "ic0"])
    ap.add_argument("--sdc", action="store_true",
                    help="also inject silent data corruption (bit flip in "
                         "p, perturbed queue copy) and show detection + "
                         "repair via the invariant checks")
    ap.add_argument("--trace", action="store_true",
                    help="thread the telemetry tracer through the staggered "
                         "scenario solve; prints the per-phase wall-time "
                         "breakdown and writes "
                         "artifacts/obs/poisson_resilient_trace.json")
    args = ap.parse_args()

    kw = dict(nx=args.nx) if args.kind != "banded" else dict(
        n=args.nx ** 3, bandwidth=16)
    problem = build_problem(args.kind, n_nodes=args.nodes,
                            precond=args.precond, **kw)
    print(f"{args.kind} M={problem.m} on {args.nodes} nodes, "
          f"precond={args.precond}")

    ref = solve_resilient(problem, strategy="none", rtol=args.rtol)
    t0 = ref.runtime_s
    print(f"reference: C={ref.converged_iter}  t0={t0:.3f}s")
    fail_at = (ref.converged_iter // 2 // args.T) * args.T + args.T - 2
    failed = list(range(args.phi))

    print(f"\n{'strategy':10s} {'scenario':12s} {'time':>8s} {'overhead':>9s} "
          f"{'recon':>7s} {'wasted':>6s}")
    for strategy, T in (("esrp", 1), ("esrp", args.T), ("imcr", args.T)):
        label = "esr" if (strategy, T) == ("esrp", 1) else strategy
        r = solve_resilient(problem, strategy=strategy, T=T, phi=args.phi,
                            rtol=args.rtol)
        print(f"{label:10s} {'failure-free':12s} {r.runtime_s:8.3f} "
              f"{100 * (r.runtime_s - t0) / t0:8.1f}% {'-':>7s} {'-':>6s}")
        r = solve_resilient(problem, strategy=strategy, T=T, phi=args.phi,
                            rtol=args.rtol, fail_at=fail_at,
                            failed_nodes=failed)
        assert r.rel_residual < args.rtol
        print(f"{label:10s} {'w/ failures':12s} {r.runtime_s:8.3f} "
              f"{100 * (r.runtime_s - t0) / t0:8.1f}% "
              f"{r.recovery_s:6.3f}s {r.wasted_iters:6d}")

    # staggered multi-event scenario: phi nodes at once, recover, then a
    # second single-node failure a period later
    scenario = [FailureEvent(fail_at, tuple(failed)),
                FailureEvent(fail_at + args.T, ((args.phi + 1) % args.nodes,))]
    r = solve_resilient(problem, strategy="esrp", T=args.T, phi=args.phi,
                        rtol=args.rtol, scenario=scenario, obs=args.trace)
    assert r.rel_residual < args.rtol
    print(f"\nstaggered scenario ({len(scenario)} events), C="
          f"{r.converged_iter}, overhead {100 * (r.runtime_s - t0) / t0:.1f}%:")
    for e in r.events:
        print(f"  iter {e.iter:4d} nodes {e.nodes}: rollback -> "
              f"{e.target_iter} ({e.wasted_iters} wasted, "
              f"{1e3 * e.recovery_s:.1f} ms reconstruction)")

    if args.trace:
        import os

        from repro.obs import span_tree, write_chrome_trace

        tr = r.trace
        os.makedirs("artifacts/obs", exist_ok=True)
        path = write_chrome_trace(
            tr, "artifacts/obs/poisson_resilient_trace.json")
        print(f"\nper-phase breakdown ({path}, {len(tr.events)} events, "
              f"push {tr.counters.get('tier_push_bytes', 0) / 1e6:.2f} MB / "
              f"fetch {tr.counters.get('tier_fetch_bytes', 0) / 1e3:.1f} KB):")

        def show(nodes, depth=0):
            # repeated phases (chunk dispatch/settle, resume) aggregate to
            # one line; each failure event expands to its full recovery tree
            agg, order = {}, []
            for n in nodes:
                if n["dur_us"] is None:
                    continue
                if n["name"].startswith("event:"):
                    order.append(("solo", n))
                    continue
                if n["name"] not in agg:
                    agg[n["name"]] = [n["cat"], 0, 0.0]
                    order.append(("agg", n["name"]))
                agg[n["name"]][1] += 1
                agg[n["name"]][2] += n["dur_us"]
            for kind, item in order:
                pad = "  " * depth
                if kind == "agg":
                    cat, calls, us = agg[item]
                    print(f"  {pad}{item:<30s}{us / 1e3:9.2f} ms  "
                          f"x{calls:<3d} [{cat}]")
                else:
                    print(f"  {pad}{item['name']:<30s}"
                          f"{item['dur_us'] / 1e3:9.2f} ms       "
                          f"[{item['cat']}]")
                    show(item["children"], depth + 1)

        roots = span_tree(tr.events)
        solve_root = roots[0] if roots else None
        if solve_root is not None:
            print(f"  {'solve':<30s}{solve_root['dur_us'] / 1e3:9.2f} ms"
                  f"       [{solve_root['cat']}]")
            show(solve_root["children"], 1)

    if args.sdc:
        xref = np.asarray(ref.x)
        xscale = max(float(np.linalg.norm(xref)), 1.0)
        print("\nsilent data corruption (detect + repair):")
        print(f"{'target':8s} {'kind':8s} {'detector':16s} {'inject':>6s} "
              f"{'caught':>6s} {'latency':>7s} {'wasted':>6s} "
              f"{'|x-xref|/|xref|':>15s}")
        for target, kind in (("p", "bitflip"), ("queue", "perturb")):
            r = solve_resilient(
                problem, strategy="esrp", T=args.T, phi=args.phi,
                rtol=args.rtol,
                scenario=[SDCEvent(iter=fail_at, nodes=(0,),
                                   target=target, kind=kind)])
            assert r.rel_residual < args.rtol
            reps = [e for e in r.events if e.kind == "sdc-repair"]
            assert len(reps) == 1, [e.kind for e in r.events]
            e = reps[0]
            err = float(np.linalg.norm(np.asarray(r.x) - xref)) / xscale
            print(f"{target:8s} {kind:8s} {e.detector:16s} {fail_at:6d} "
                  f"{e.detect_iter:6d} {e.detect_latency:7d} "
                  f"{e.wasted_iters:6d} {err:15.2e}")
        print("  (queue corruption costs zero wasted iterations: the copy "
              "is re-pushed\n   from live state without touching the "
              "iteration)")


if __name__ == "__main__":
    main()
