"""Readings that the correctness limits of a cell are set from, in one
process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 [--solves 2]
        [--precisions highest,high,default]
        [--faults lost_share_zeroed,direction_lost] [--fault-seeds 3]
    python3 bench/calibrate.py --workload <cell> --derive

For each seed it does what a run of the cell does with that seed: the same
right-hand sides and failures, and the first ``--solves`` solves of the
window through the timed path, judged by the run's own check. Then, on the
same right-hand sides, the plain reference solve at each precision in
``--precisions`` put in the program's place and judged by the same check:
``highest`` (the reference itself) and the lower-precision controls. Then,
on the first ``--fault-seeds`` seeds, the program again with each recovery
fault of ``bench/faults.py`` planted. Each line is one JSON object; the
limit of each number lies between the largest reading of the program and
the smallest of the controls or faults.

``--derive`` prints the failure-free iteration count C of the plain
reference on the warm-up vector, and the failure iteration that the
traffic file's ``derived_from`` parameters give with it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import faults, generator, harness, reference, spec  # noqa: E402


def emit(**kw) -> None:
    print(json.dumps(kw), flush=True)


def readings(numbers: dict) -> dict:
    return {k: v for k, (v, _) in numbers.items()}


def derive(cell, ref, warm) -> None:
    cfg = cell.config
    _, c = ref.answer("warm", np.asarray(warm))
    out = []
    for f in cell.traffic["failures"]:
        d = f["derived_from"]
        out.append(generator.failure_iteration(
            c, int(cfg["T"]), float(d["fraction_of_C"]),
            int(d["before_stage_end"])))
    emit(workload=cell.name, C=c, failure_iterations=out,
         stated=[f["iteration"] for f in cell.traffic["failures"]])


def main(argv=None, root: Path = ROOT, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--solves", type=int, default=1)
    ap.add_argument("--precisions", default="highest,high,default")
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--derive", action="store_true")
    args = ap.parse_args(argv)
    import jax

    cell = spec.resolve(args.workload, root)
    cfg = cell.config
    jax.config.update("jax_enable_x64", True)
    try:
        dev = harness.device_info(cell.chips, require_chip)
    except harness.NoChip as e:
        harness.log(f"[device] {e}")
        return 2
    harness.use_cache(root)
    emit(device=dev, workload=cell.name)
    op = spec.operator(cell)
    dtype = np.dtype(cfg["dtype"])
    ref = harness.Reference.build(cell)
    if args.derive:
        _, warm = generator.rhs_pool(cell.traffic, op, cfg, 0, op.size(cfg),
                                     dtype)
        derive(cell, ref, warm)
        return 0
    problem = harness.build(cell)
    failures = generator.failure_schedule(cell.traffic)
    seeds = [int(s) for s in args.seeds.split(",")]
    pools = {}
    for seed in seeds:
        pool, _ = generator.rhs_pool(cell.traffic, op, cfg, seed, problem.m,
                                     dtype)
        pools[seed] = pool[:args.solves]

    def program(seed, side):
        pool, keys = pools[seed], [(seed, k) for k in range(args.solves)]
        solves = []
        for k in range(args.solves):
            t = time.perf_counter()
            try:
                rep = harness.solve(problem, cell, pool[k], failures)
                jax.block_until_ready(rep.x)
            except Exception as e:          # an answer that never comes
                harness.log(f"[{side}] seed {seed} raised {e!r}")
                rep = None
            solves.append(harness.Solve(k, time.perf_counter() - t, rep))
        numbers, failed = harness.check(cell, solves, pool, failures, ref,
                                        keys)
        reps = [s.report for s in solves if s.report is not None]
        emit(seed=seed, side=side, failures=failures, failed=failed,
             iters=[r.converged_iter for r in reps],
             wasted=[r.wasted_iters for r in reps],
             targets=[r.target_iter for r in reps],
             seconds=[s.seconds for s in solves], numbers=readings(numbers))

    for seed in seeds:
        program(seed, "program")
    for fault in filter(None, args.faults.split(",")):
        undo = faults.plant(fault)
        try:
            for seed in seeds[:args.fault_seeds]:
                program(seed, f"fault:{fault}")
        finally:
            undo()
    del problem
    for seed in seeds:
        pool, keys = pools[seed], [(seed, k) for k in range(args.solves)]
        for prec in args.precisions.split(","):
            solves = []
            for k, b in enumerate(pool):
                x, it, rel = reference.solve(op, cfg, ref.pinv, b,
                                             float(cfg["rtol"]), prec)
                solves.append(harness.Solve(k, 0.0, harness.control_report(
                    x, it, rel, failures, cfg)))
            numbers, failed = harness.check(cell, solves, pool, failures, ref,
                                            keys)
            emit(seed=seed, side=f"reference:{prec}", failed=failed,
                 iters=[s.report.converged_iter for s in solves],
                 numbers=readings(numbers))
    return 0


if __name__ == "__main__":
    # the TPU runtime writes its logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
