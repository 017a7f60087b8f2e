"""Chip smoke test: the resilient PCG solve and the solver service on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the mesh failure runtime

One chip: the main-path Pallas kernels against their jnp references at a
small size, then ``poisson3d`` at 128³ (M = 2,097,152 unknowns, 16 logical
nodes, block-Jacobi) built in float32, solved through ``solve_resilient``
with ``backend="pallas"`` — unprotected, ESRP failure-free, ESRP through a
φ = 2 fail-stop recovered by Alg. 2, IMCR through the same failure — and a
``SolverService`` answering batched requests with a failure injected. Four
chips: the operator sharded over ``nodes_mesh(4)``, ESRP through a one-node
fail-stop on ``ShardedFailureRuntime``, against its single-device
``mesh_mirror_ops`` mirror.

Every check raises on failure; nothing is caught. The last line of standard
output is the JSON result, printed only when every check held. Without a
TPU the script exits with status 2 before any phase.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

# f32 on the chip (Mosaic compiles 32-bit kernels only)
DTYPE = np.float32
BACKEND = "pallas"
NX = 128                 # 128³ = 2,097,152 unknowns
TILE = 128               # Block-ELL bm = bn: one lane-dense tile per step
PRECOND_BLOCK = 8        # block-Jacobi block; divides the 128 lanes
NODES = 16               # the paper's logical node count
T, PHI = 20, 2
# Convergence target on the recursive residual ||r||/||b||: f32 CG reaches
# it (measured 118 iterations at 48³ on the CPU, jnp backend).
RTOL = 1e-6
# Bound on the true residual ||b - Ax||/||b||, computed on the host in f64
# from the COO triples. In f32 the recursive residual drifts from the true
# one by accumulated rounding, about sqrt(k) * eps * ||A|| ||x|| / ||b||
# after k iterations (eps = 6e-8); 100x the target leaves room for that
# over a few hundred iterations, and still fails a kernel whose products
# are rounded to bf16 (eps = 4e-3) or otherwise wrong.
TRUE_BOUND = 100 * RTOL
# Recovery through Alg. 2 restores the trajectory up to f32 rounding, so
# the recovered iteration count may move by a few iterations.
ITER_SLACK = 5
REQUESTS, BATCH = 8, 4
# The four-chip phase keeps the whole operator on chip 0 as well (the
# single-device mirror), next to its own slab and both runs' Alg. 2
# strips: 96³ (M = 884,736) leaves that chip headroom.
MESH_NX = 96


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def device_info():
    import jax
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def true_residual(problem, x, rhs=None) -> float:
    """||b - A x|| / ||b|| in f64 from the COO triples on the host (scipy):
    an oracle independent of the Block-ELL layout and of every kernel."""
    import scipy.sparse as sp

    csr = getattr(problem, "_host_csr", None)
    if csr is None:
        rows, cols, vals = problem.coo
        csr = sp.csr_matrix((vals.astype(np.float64), (rows, cols)),
                            shape=(problem.m, problem.m))
        problem._host_csr = csr
    b = np.asarray(problem.b if rhs is None else rhs, np.float64)
    return float(np.linalg.norm(b - csr @ np.asarray(x, np.float64))
                 / np.linalg.norm(b))


def timed(label: str, fn):
    t = time.perf_counter()
    out = fn()
    log(f"[time] {label}: {time.perf_counter() - t:.3f} s")
    return out


# --------------------------------------------------------------------------- #
# one chip
# --------------------------------------------------------------------------- #
def _rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def kernels_vs_reference() -> None:
    """The Pallas bundle against the jnp bundle on a small problem with the
    chip geometry, unbatched and at B = BATCH, and the SpMV against the COO
    product in f64. The jnp side runs at full f32 matmul precision, as
    ``solve_resilient`` runs it (a TPU's default is one bf16 pass)."""
    import jax

    from repro.sparse.matrices import build_problem

    p = build_problem("poisson3d", n_nodes=4, nx=16, bm=TILE, bn=TILE,
                      precond_block=PRECOND_BLOCK, dtype=DTYPE)
    rng = np.random.default_rng(0)
    rows, cols, vals = p.coo
    with jax.default_matmul_precision("highest"):
        for batch in (0, BATCH):
            shape = (batch, p.m) if batch else (p.m,)
            ker = p.solver_ops(BACKEND, batch=batch)
            ref = p.solver_ops("jnp", batch=batch)
            check(ker.backend == BACKEND, f"bundle backend {ker.backend}")
            vs = [jax.numpy.asarray(rng.standard_normal(shape), DTYPE)
                  for _ in range(5)]
            alpha = jax.numpy.asarray(rng.uniform(0.1, 1.0, batch or ()),
                                      DTYPE)
            mv = ker.matvec(vs[0])
            x0 = np.asarray(vs[0][0] if batch else vs[0], np.float64)
            ax = np.zeros(p.m)
            np.add.at(ax, rows, vals * x0[cols])       # the COO product
            errs = {
                "matvec": _rel(mv, ref.matvec(vs[0])),
                "matvec_vs_coo": _rel(mv[0] if batch else mv, ax),
                "matvec_dot": max(_rel(a, b) for a, b in zip(
                    ker.matvec_dot(vs[0]), ref.matvec_dot(vs[0]))),
                "update": max(_rel(a, b) for a, b in zip(
                    ker.update(alpha, *vs[1:]), ref.update(alpha, *vs[1:]))),
            }
            log(f"[kernels] B={batch or 1} rel. error vs reference: "
                + ", ".join(f"{k} {v:.2e}" for k, v in errs.items()))
            check(max(errs.values()) < 1e-5, f"kernel mismatch at B={batch}")


def chunk_runner_is_f32_pallas(problem, ops) -> None:
    """Lower the jitted ESRP chunk runner as the driver calls it: the
    operator must arrive as an argument, not as an embedded constant; no
    f64 vector may appear (a stray float64 scalar promotes the vectors);
    and the kernels must be Mosaic calls, not interpreted or jnp code."""
    import jax
    import jax.numpy as jnp

    from repro.core import esrp

    st = esrp.esrp_init(ops.matvec, ops.precond, problem.b, dot=ops.dot)
    thresh = jnp.asarray(RTOL, problem.b.dtype)
    t = time.perf_counter()
    lowered = esrp.run_chunk.lower(st, ops, T, 64, thresh)
    text = lowered.as_text()
    lowered.compile()
    log(f"[compile] ESRP chunk runner (64 iterations): "
        f"{time.perf_counter() - t:.3f} s")
    f64 = sorted(set(re.findall(r"tensor<(?:\d+x)+f64>", text)))
    calls = text.count("tpu_custom_call")
    args = [a.shape for a in jax.tree.leaves(lowered.args_info)]
    log(f"[lowering] {len(text)} characters, {len(args)} arguments; f64 "
        f"vector types: {f64 or 'none'}; Mosaic kernel calls: {calls}")
    check(problem.a.data.shape in args,
          "the Block-ELL matrix is not an argument of the chunk runner")
    check(len(text) < problem.a.data.nbytes // 100,
          "the chunk runner embeds the operator as a constant")
    check(not f64, f"f64 vector ops in the chunk runner: {f64}")
    check(calls >= 2, "the chunk runner holds no Mosaic kernel call")


def solves(problem) -> None:
    from repro.core.driver import solve_resilient
    from repro.core.failures import FailureEvent

    log(f"[solve] target ||r||/||b|| < {RTOL:g}; true-residual bound "
        f"{TRUE_BOUND:g} (host f64 from the COO)")

    def run(label, **kw):
        r = timed(label, lambda: solve_resilient(
            problem, rtol=RTOL, backend=BACKEND, **kw))
        tr = true_residual(problem, r.x)
        log(f"[solve] {label}: converged={r.converged} C={r.converged_iter} "
            f"rel={r.rel_residual:.3e} true={tr:.3e} "
            f"recovery={r.recovery_s:.3f} s wasted={r.wasted_iters}")
        check(r.converged and r.rel_residual < RTOL, f"{label} not converged")
        check(tr < TRUE_BOUND, f"{label}: true residual {tr:.3e}")
        return r

    ref = run("reference (strategy=none)", strategy="none")
    C = ref.converged_iter
    run(f"ESRP T={T} phi={PHI} failure-free", strategy="esrp", T=T, phi=PHI)
    # two iterations before the storage stage holding C/2 (the paper's
    # worst case): the recovery rolls back to the previous stage
    fail = (C // 2 // T) * T + T - 2
    event = [FailureEvent(fail, tuple(range(5, 5 + PHI)))]
    log(f"[solve] fail-stop of nodes {event[0].nodes} at iteration {fail}")
    esrp_f = run("ESRP with failure", strategy="esrp", T=T, phi=PHI,
                 scenario=event)
    d = esrp_f.converged_iter - C
    log(f"[solve] ESRP recovered iterations - reference: {d:+d}")
    check(esrp_f.events and esrp_f.events[0].target_iter >= 0,
          "ESRP did not reconstruct through Alg. 2")
    check(abs(d) <= ITER_SLACK, f"ESRP recovery moved C by {d}")
    imcr_f = run("IMCR with failure", strategy="imcr", T=T, phi=PHI,
                 scenario=event)
    log(f"[solve] IMCR iterations - reference: "
        f"{imcr_f.converged_iter - C:+d}")


def service(problem, requests: int = REQUESTS) -> None:
    from repro.core.failures import FailureEvent
    from repro.serve.solver_service import SolverService

    rng = np.random.default_rng(1)
    # the failure strikes the first micro-batch only
    svc = SolverService(problem, batch=BATCH, strategy="esrp", T=T, phi=PHI,
                        rtol=RTOL, backend=BACKEND,
                        scenario=[FailureEvent(2 * T - 2, (1, 2))],
                        fail_every=requests)
    rhs = [rng.standard_normal(problem.m).astype(DTYPE)
           for _ in range(requests)]
    ids = [svc.submit(v) for v in rhs]
    t = time.perf_counter()
    svc.run()
    wall = time.perf_counter() - t
    worst = 0.0
    for rid, v in zip(ids, rhs):
        res = svc.results[rid]
        rep = res.report
        check(res.status == "ok" and rep is not None and rep.converged,
              f"request {rid}: {res.status}")
        tr = true_residual(problem, rep.x, v)
        worst = max(worst, tr)
        check(tr < TRUE_BOUND, f"request {rid}: true residual {tr:.3e}")
    events = sum(len(svc.results[i].report.events) for i in ids)
    log(f"[service] {requests} requests at B={BATCH}: all ok, "
        f"{events} member event report(s), worst true residual "
        f"{worst:.3e}, wall {wall:.3f} s")
    check(events > 0, "the injected failure never struck")


def one_chip(nx: int) -> None:
    from repro.sparse.matrices import build_problem

    timed("kernels vs reference", kernels_vs_reference)
    problem = timed("host build", lambda: build_problem(
        "poisson3d", n_nodes=NODES, nx=nx, bm=TILE, bn=TILE,
        precond_block=PRECOND_BLOCK, dtype=DTYPE))
    check(problem.m >= 128 ** 3, "M below 128³ = 2,097,152")
    ops = problem.solver_ops(BACKEND)
    log(f"[ops] hot-loop bundle backend: {ops.backend}")
    check(ops.backend == BACKEND, "wrong bundle")
    a = problem.a
    data, idx, dia, pinv = ops.operands
    log(f"[problem] poisson3d {nx}^3: M={problem.m} dtype={problem.b.dtype} "
        f"nodes={NODES} tiles {a.bm}x{a.bn} kmax={a.kmax} on "
        f"{a.data.devices()}; bytes on the device: Block-ELL "
        f"{data.nbytes + idx.nbytes + a.nblk.nbytes}, block-Jacobi "
        f"diagonals {dia.nbytes} (the fused update reads these), inverse "
        f"and diagonal blocks {pinv.nbytes + problem.diag_blocks.nbytes}")
    chunk_runner_is_f32_pallas(problem, ops)
    timed("solves", lambda: solves(problem))
    timed("service", lambda: service(problem))


# --------------------------------------------------------------------------- #
# four chips
# --------------------------------------------------------------------------- #
def mesh_phase(nx: int, n_chips: int) -> None:
    import jax

    from repro.comm.shard import (ShardedFailureRuntime, mesh_mirror_ops,
                                  nodes_mesh, place_problem,
                                  sharded_solver_ops)
    from repro.core.driver import solve_resilient
    from repro.core.failures import FailureEvent
    from repro.sparse.matrices import build_problem

    problem = timed("host build", lambda: build_problem(
        "poisson3d", n_nodes=n_chips, nx=nx, bm=TILE, bn=TILE,
        precond_block=PRECOND_BLOCK, dtype=DTYPE))
    mesh = nodes_mesh(n_chips)
    placed = timed("placement", lambda: place_problem(problem, mesh))
    per_dev = {}
    for sh in placed.a.data.addressable_shards:
        per_dev[str(sh.device)] = sh.data.nbytes
    log(f"[mesh] M={problem.m}, operator bytes per device: {per_dev}")
    check(len(per_dev) == n_chips
          and len(set(per_dev.values())) == 1
          and sum(per_dev.values()) == problem.a.data.nbytes,
          "the operator is not split into one slab per chip")
    mirror = mesh_mirror_ops(problem, n_chips)
    with mesh:
        ops = sharded_solver_ops(placed, mesh)
    ref = timed("mirror reference", lambda: solve_resilient(
        problem, strategy="none", rtol=RTOL, ops=mirror))
    C = ref.converged_iter
    event = [FailureEvent((C // 2 // T) * T + T - 2, (1,))]
    frt = ShardedFailureRuntime(placed, mesh)

    def sharded():
        with mesh:
            return solve_resilient(placed, strategy="esrp", T=T, phi=1,
                                   rtol=RTOL, ops=ops, scenario=list(event),
                                   failure_runtime=frt)

    r = timed("mesh ESRP with failure", sharded)
    rm = timed("mirror ESRP with failure", lambda: solve_resilient(
        problem, strategy="esrp", T=T, phi=1, rtol=RTOL, ops=mirror,
        scenario=list(event)))
    xm, xr = np.asarray(r.x, np.float64), np.asarray(rm.x, np.float64)
    dx = float(np.linalg.norm(xm - xr) / np.linalg.norm(xr))
    tr, trm = true_residual(problem, r.x), true_residual(problem, rm.x)
    ev = r.events[0] if r.events else None
    log(f"[mesh] fail-stop of node 1 at iteration {event[0].iter}: mesh "
        f"C={r.converged_iter} mirror C={rm.converged_iter} reference C={C}; "
        f"recovered from devices {ev.queue_src_nodes if ev else None}; "
        f"||x_mesh - x_mirror||/||x_mirror|| = {dx:.3e} "
        f"(bitwise equal: {bool((xm == xr).all())}); true residual "
        f"mesh {tr:.3e} mirror {trm:.3e}")
    check(r.converged and rm.converged, "mesh or mirror did not converge")
    check(ev is not None and ev.target_iter >= 0 and ev.queue_src_nodes
          and 1 not in ev.queue_src_nodes,
          "the mesh did not recover from surviving devices' queue shards")
    check(abs(r.converged_iter - rm.converged_iter) <= ITER_SLACK,
          "mesh and mirror iteration counts diverged")
    check(dx < TRUE_BOUND and tr < TRUE_BOUND and trm < TRUE_BOUND,
          "mesh solution does not rejoin its mirror")
    check(len(jax.devices()) >= n_chips, "devices vanished")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the mesh phase, on four chips")
    args = ap.parse_args()

    import jax

    from repro.launch.compile_cache import use_compile_cache

    # the entry points' configuration: x64 on (index maps must survive it)
    jax.config.update("jax_enable_x64", True)
    cache = use_compile_cache()
    info = device_info()
    log(f"[device] jax {jax.__version__} platform={info['platform']} "
        f"kind={info['kind']} count={info['count']} compile cache {cache}")
    if info["platform"] != "tpu":
        print("no TPU: JAX sees only " + info["platform"], file=sys.stderr)
        return 2
    if info["count"] < args.chips:
        print(f"{args.chips} chips asked, {info['count']} present",
              file=sys.stderr)
        return 2
    t = time.perf_counter()
    if args.chips == 4:
        mesh_phase(MESH_NX, 4)
    else:
        one_chip(NX)
    log(f"[time] total: {time.perf_counter() - t:.3f} s")
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
