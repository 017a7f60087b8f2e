"""Benchmark entry point — one function per paper table/figure.

``python -m benchmarks.run``            reduced grid (CI-sized, ~10 min)
``python -m benchmarks.run --full``     the paper's full T x phi x location
                                        grid, 5 repetitions (~1 h on 1 core)
``python -m benchmarks.run --only X``   X in {only_choices}
                                        (derived from ``ALL`` below — add a
                                        benchmark there and this list follows)

Output: CSV blocks ``name,us_per_call,derived`` per the harness convention,
plus the full tables to artifacts/bench/.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np


def _ensure_dir():
    os.makedirs("artifacts/bench", exist_ok=True)


def bench_paper_table(table: str, full: bool):
    """Tables 2/3 + Figs 2/3: ESRP vs ESR(T=1) vs IMCR overheads."""
    import jax
    jax.config.update("jax_enable_x64", True)
    from benchmarks.paper_tables import format_rows, run_table

    if table == "table2":
        kind, kw = "poisson2d", dict(nx=192)
    else:
        kind, kw = "poisson3d", dict(nx=32)
    Ts = (1, 20, 50, 100) if full else (1, 20, 50)
    phis = (1, 3, 8) if full else (1, 3)
    reps = 5 if full else 3
    t_start = time.time()
    t0, C, rows = run_table(kind, kw, Ts=Ts, phis=phis, reps=reps)
    text = format_rows(f"{table} ({kind} surrogate)", t0, C, rows)
    _ensure_dir()
    with open(f"artifacts/bench/{table}.csv", "w") as f:
        f.write(text + "\n")
    print(text)
    # harness CSV: the paper's headline setting (T=20, phi=1)
    sel = [r for r in rows if r.T == 20 and r.phi == 1]
    for r in sel:
        print(f"{table}_{r.strategy}_T{r.T}_phi{r.phi}_{r.scenario},"
              f"{1e6 * r.runtime_s:.0f},overhead_pct={100 * r.overhead:.2f}")
    print(f"# {table} wall {time.time() - t_start:.0f}s")


def bench_table4(full: bool):
    """Residual drift (paper Eq. 2 / Table 4)."""
    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core.driver import solve_resilient
    from repro.sparse.matrices import build_problem

    out = []
    for name, kind, kw in (("poisson2d_192", "poisson2d", dict(nx=192)),
                           ("poisson3d_32", "poisson3d", dict(nx=32))):
        p = build_problem(kind, n_nodes=16, **kw)
        ref = solve_resilient(p, strategy="none", rtol=1e-8, chunk=128)
        drifts = []
        C = ref.converged_iter
        for loc in (0, 8):
            for phi in (1, 3):
                failed = [(loc + i) % 16 for i in range(phi)]
                r = solve_resilient(p, strategy="esrp", T=20, phi=phi,
                                    rtol=1e-8, chunk=128,
                                    fail_at=(C // 2 // 20) * 20 + 18,
                                    failed_nodes=failed)
                drifts.append(r.drift)
        row = (f"table4_{name},0,reference={ref.drift:.3e};"
               f"median={np.median(drifts):.3e};min={np.min(drifts):.3e}")
        out.append(row)
        print(row)
    _ensure_dir()
    with open("artifacts/bench/table4.csv", "w") as f:
        f.write("\n".join(out) + "\n")


def bench_volume():
    """Communication-volume model (paper §2.2.1/§3.1, exact)."""
    import jax
    jax.config.update("jax_enable_x64", True)
    from benchmarks.paper_tables import comm_volume_table

    for name, kind, kw in (("poisson2d_192", "poisson2d", dict(nx=192)),
                           ("poisson3d_32", "poisson3d", dict(nx=32))):
        for row in comm_volume_table(kind, kw):
            print(f"volume_{name}_phi{row['phi']},0,"
                  f"spmv={row['spmv_bytes']};aspmv={row['aspmv_bytes']};"
                  f"esrp_stage={row['esrp_stage_bytes']};"
                  f"imcr_ckpt={row['imcr_ckpt_bytes']}")


def bench_kernels():
    """Kernel validation sweeps + jnp-path timing (us/call)."""
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from repro.kernels.spmv.ops import blockell_matvec
    from repro.kernels.spmv.ref import spmv_ref
    from repro.kernels.fused_pcg.ops import pcg_update
    from repro.sparse.matrices import build_problem

    p = build_problem("poisson3d", n_nodes=16, nx=32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(p.m))
    y_ref = spmv_ref(p.a.data, p.a.idx, x)
    y_ker = blockell_matvec(p.a, x, backend="interpret")
    err = float(jnp.abs(y_ref - y_ker).max())
    assert err < 1e-10, err
    f = jax.jit(lambda v: spmv_ref(p.a.data, p.a.idx, v))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(50):
        y = f(x)
    y.block_until_ready()
    us = (time.perf_counter() - t0) / 50 * 1e6
    nnz = float(np.sum(np.asarray(p.a.nblk)) * p.a.bm * p.a.bn)
    print(f"kernel_spmv,{us:.0f},interpret_err={err:.1e};gflops="
          f"{2 * nnz / us / 1e3:.2f}")

    alpha = jnp.asarray(0.3)
    r, q, pv = x, x * 0.5, x * 0.25
    ref = pcg_update(alpha, x, r, pv, q, p.pinv_blocks, backend="jnp")
    ker = pcg_update(alpha, x, r, pv, q, p.pinv_blocks, backend="interpret",
                     rows=160)
    err = max(float(jnp.abs(a - b).max()) for a, b in zip(ref, ker))
    g = jax.jit(lambda a, x_, r_, p_, q_: pcg_update(
        a, x_, r_, p_, q_, p.pinv_blocks, backend="jnp"))
    g(alpha, x, r, pv, q)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(50):
        o = g(alpha, x, r, pv, q)
    o[0].block_until_ready()
    us = (time.perf_counter() - t0) / 50 * 1e6
    print(f"kernel_fused_pcg,{us:.0f},interpret_err={err:.1e}")


def bench_ft(trace=False):
    """ESRP-for-training overheads (us/step, push volume per stage).
    Timing routes through a span tracer (one ``measure:ft_*`` span per
    config); ``trace=True`` also threads it into the trainer (storage/
    recovery spans, per-step loss counter) and exports artifacts/obs/
    ft_trace.json."""
    import jax
    from repro.configs import smoke_config
    from repro.models.lm import LM
    from repro.obs import Tracer, write_chrome_trace
    from repro.train.optimizer import AdamWConfig, init_opt_state
    from repro.train.train_step import make_train_step
    from repro.data.pipeline import TokenPipeline
    from repro.ft.esrp_trainer import ESRPTrainer, FTConfig

    tracer = Tracer("bench_ft")
    cfg = smoke_config("internlm2_1_8b")
    model = LM(cfg)
    params, specs = model.init(jax.random.PRNGKey(0))
    opt = init_opt_state(params)
    ts = make_train_step(model, AdamWConfig(warmup_steps=4))
    pipe = TokenPipeline(cfg, global_batch=4, seq_len=64, seed=7)
    for mode, compress in (("none", False), ("esrp", False),
                           ("esrp", True), ("imcr", False)):
        label = mode + ("_bf16" if compress else "")
        tr = ESRPTrainer(model, ts, pipe,
                         FTConfig(mode=mode, T=10, phi=1, n_ranks=8,
                                  compress=compress), specs,
                         obs=tracer if trace else None)
        with tracer.span(f"warmup:ft_{label}", cat="warmup"):
            tr.run(params, opt, n_steps=3)    # warmup: amortize jit compile
        tr.push_bytes = tr.push_count = 0
        with tracer.span(f"measure:ft_{label}", cat="measure") as m_sp:
            tr.run(params, opt, n_steps=40)
        dt = m_sp.dur_s
        print(f"ft_{label},{1e6 * dt / 40:.0f},"
              f"push_MB_per_stage="
              f"{tr.push_bytes / max(tr.push_count, 1) / 1e6:.2f}")
    if trace:
        os.makedirs("artifacts/obs", exist_ok=True)
        path = write_chrome_trace(tracer, "artifacts/obs/ft_trace.json")
        print(f"# wrote {path} ({len(tracer.events)} events)")


def bench_iteration(full: bool):
    """Per-iteration hot-loop microbenchmark (us/iteration) for the three
    execution configurations this repo's perf trajectory tracks:

      jnp         seed path: unfused closure ops (einsum SpMV, separate pᵀq
                  and rᵀz dots) + jnp.where storage bookkeeping
      fused       SolverOps bundle (fused SpMV+dot, fused x/r/z/rz update),
                  still where-gated
      fused_cond  the full PR: fused bundle + lax.cond-gated queue push /
                  star capture / residual replacement

    Rows ``iteration_<config>`` use rr_every=0 (the paper's setting);
    ``iteration_<config>_rr10`` adds residual replacement every 10 iterations
    — the case where cond-gating removes a whole SpMV+precond from 9 of
    every 10 iterations.
    """
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    from repro.core import esrp
    from repro.core.ops import make_closure_ops
    from repro.sparse.matrices import build_problem

    kind, kw = ("poisson3d", dict(nx=32)) if full else \
        ("poisson2d", dict(nx=96))
    p = build_problem(kind, n_nodes=16, **kw)
    T, n_iters, reps = 20, 100, 5
    thresh = jnp.asarray(-1.0, p.b.dtype)      # never freezes: pure hot loop

    configs = (
        ("jnp", make_closure_ops(p.a.matvec, p.apply_precond), False),
        ("fused", p.solver_ops("jnp"), False),
        ("fused_cond", p.solver_ops("jnp"), True),
    )
    out = []
    for rr in (0, 10):
        for name, ops, gated in configs:
            run = lambda s: esrp.run_chunk(s, ops, T, n_iters, thresh,
                                           rr, gated, p.b)
            st = esrp.esrp_init(ops.matvec, ops.precond, p.b)
            run(st)[1].block_until_ready()     # compile
            best = float("inf")
            for _ in range(reps):
                st_r = esrp.esrp_init(ops.matvec, ops.precond, p.b)
                t0 = time.perf_counter()
                _, norms = run(st_r)
                norms.block_until_ready()
                best = min(best, time.perf_counter() - t0)
            us = best / n_iters * 1e6
            label = f"iteration_{name}" + (f"_rr{rr}" if rr else "")
            out.append((label, us))
            print(f"{label},{us:.1f},m={p.m};T={T};gated={int(gated)}")
    base = dict(out)[f"iteration_jnp"]
    winner = dict(out)[f"iteration_fused_cond"]
    print(f"iteration_speedup,0,fused_cond_vs_jnp={base / winner:.3f}x")
    _ensure_dir()
    with open("artifacts/bench/iteration.csv", "w") as f:
        f.writelines(f"{k},{v:.1f}\n" for k, v in out)


def bench_roofline():
    """Roofline terms per dry-run cell (from artifacts/dryrun)."""
    from repro.roofline.report import summarize
    for line in summarize("artifacts/dryrun"):
        print(line)


def bench_precond(full):
    """Preconditioner x T x failure-location sweep — the experiment the
    paper's conclusion proposes ("more appropriate preconditioners") but
    never runs: iterations-to-converge, per-iteration cost, wall-clock, and
    recovery overhead for block-Jacobi vs SSOR vs Chebyshev vs IC(0),
    including the anisotropic poisson3d regime where block-Jacobi struggles
    and the denser banded family (audikw_1 regime). Emits a wall-clock
    winner per problem (meaningful now that the sweeps route through the
    wavefront kernels when the elimination DAG allows) and a
    machine-readable BENCH_precond.json next to the CSV."""
    import json

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core.driver import solve_resilient
    from repro.sparse.matrices import build_problem

    problems = [("poisson2d", "poisson2d", dict(nx=64 if full else 48)),
                ("poisson3d_aniso", "poisson3d",
                 dict(nx=16 if full else 12, eps=0.25)),
                ("banded", "banded",
                 dict(n=2400 if full else 1600, bandwidth=16, density=0.4))]
    preconds = ("jacobi", "ssor", "chebyshev", "ic0")
    Ts = (10, 20, 50) if full else (10, 20)
    lines = ["problem,precond,T,scenario,iters,us_per_iter,runtime_ms,sweep,"
             "recovery_ms,wasted,rel_residual"]
    iters_aniso = {}
    wall: dict[str, dict[str, float]] = {}
    rows_json = []
    for pname, kind, kw in problems:
        wall[pname] = {}
        for name in preconds:
            p = build_problem(kind, n_nodes=8, precond=name, **kw)
            # the timed run resolves backend "auto" to jnp on this CPU host,
            # which executes the sequential sweep; "(wavefront-ready)" marks
            # structures whose kernel backends would take the level grid
            sweep_kind = "-"
            if name in ("ssor", "ic0"):
                sweep_kind = ("sequential(wavefront-ready)"
                              if p.precond.lo_wf is not None
                              else "sequential")
            solve_resilient(p, strategy="none", rtol=1e-8, chunk=32)  # warmup
            ref = solve_resilient(p, strategy="none", rtol=1e-8, chunk=32)
            C = ref.converged_iter
            us = 1e6 * ref.runtime_s / max(C, 1)
            wall[pname][name] = ref.runtime_s
            if pname == "poisson3d_aniso":
                iters_aniso[name] = C
            lines.append(f"{pname},{name},-,failure-free,{C},{us:.1f},"
                         f"{1e3 * ref.runtime_s:.1f},{sweep_kind},-,-,"
                         f"{ref.rel_residual:.2e}")
            rows_json.append(dict(problem=pname, precond=name, iters=C,
                                  us_per_iter=us,
                                  runtime_ms=1e3 * ref.runtime_s,
                                  sweep=sweep_kind,
                                  rel_residual=ref.rel_residual))
            print(f"precond_{pname}_{name},{us:.1f},iters={C};"
                  f"sweep={sweep_kind}")
            # warm the recovery path once (jitted reconstruction closures,
            # scatter kernels) so recovery_ms rows measure reconstruction,
            # not one-off compiles
            if 2 * Ts[0] < C:
                solve_resilient(p, strategy="esrp", T=Ts[0], phi=1,
                                rtol=1e-8, chunk=32, fail_at=2 * Ts[0],
                                failed_nodes=[1])
            for T in Ts:
                scens = {"early": 2 * T, "mid": (C // 2 // T) * T}
                if scens["mid"] <= scens["early"]:
                    del scens["mid"]       # would duplicate the early config
                for scen, fail_at in scens.items():
                    if fail_at >= C:
                        continue
                    r = solve_resilient(p, strategy="esrp", T=T, phi=1,
                                        rtol=1e-8, chunk=32,
                                        fail_at=fail_at, failed_nodes=[1])
                    # us_per_iter only for failure-free rows: failed runs
                    # pay one-off jit compiles for the post-failure chunk
                    # tails, which would misread as per-iteration cost
                    lines.append(
                        f"{pname},{name},{T},{scen}@{fail_at},"
                        f"{r.converged_iter},-,-,-,"
                        f"{1e3 * r.recovery_s:.2f},{r.wasted_iters},"
                        f"{r.rel_residual:.2e}")
    best = min((n for n in preconds if n != "jacobi"),
               key=lambda n: iters_aniso[n])
    print(f"precond_best_aniso,0,winner={best};iters={iters_aniso[best]};"
          f"jacobi_iters={iters_aniso['jacobi']}")
    winners = {}
    for pname in wall:
        w = min(wall[pname], key=wall[pname].get)
        winners[pname] = dict(winner=w, runtime_ms=1e3 * wall[pname][w])
        print(f"precond_wallclock_{pname},{1e6 * wall[pname][w]:.0f},"
              f"winner={w}")
    _ensure_dir()
    with open("artifacts/bench/precond.csv", "w") as f:
        f.write("\n".join(lines) + "\n")
    with open("artifacts/bench/BENCH_precond.json", "w") as f:
        json.dump(dict(problems={n: kw for n, _, kw in problems},
                       rows=rows_json, wallclock_winners=winners,
                       aniso_iter_winner=dict(
                           winner=best, iters=iters_aniso[best],
                           jacobi_iters=iters_aniso["jacobi"])),
                  f, indent=1, default=float)
    print("# wrote artifacts/bench/precond.csv + BENCH_precond.json")


def bench_recovery(full):
    """Alg. 2 reconstruction microbench per preconditioner: recovery
    wall-clock and line-6 inner-CG iteration count with the unpreconditioned
    (historical) vs preconditioned P_ff solve — the recovery cost Pachajoa
    et al. (arXiv:1907.13077) find dominated by the preconditioner-shaped
    inner solves. Warm runs (reconstruction closures jitted by a throwaway
    first run, same policy as the precond sweep); every row must rejoin the
    failure-free trajectory exactly. Writes artifacts/bench/recovery.csv +
    BENCH_recovery.json.
    """
    import json

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core.driver import solve_resilient
    from repro.sparse.matrices import build_problem

    # ic0 runs on the anisotropic poisson3d grid: on poisson2d its block
    # pattern is tridiagonal, the factorization is exact (P = A⁻¹ to fp),
    # and the whole convergence tail is rounding-driven — no stable rejoin
    # point exists for a recovery experiment there
    configs = [("poisson2d", dict(nx=64 if full else 48),
                ("jacobi", "ssor", "chebyshev")),
               ("poisson3d", dict(nx=16 if full else 12, eps=0.25),
                ("ic0",))]
    lines = ["problem,precond,pff_precond,T,fail_at,iters,recovery_ms,"
             "pff_iters,inner_rel,exact_rejoin"]
    rows = []
    runs = [(kind, kw, name) for kind, kw, preconds in configs
            for name in preconds]
    for kind, kw, name in runs:
        p = build_problem(kind, n_nodes=8, precond=name, **kw)
        ref = solve_resilient(p, strategy="none", rtol=1e-8, chunk=32)
        C = ref.converged_iter
        # one completed storage stage before the failure, failure well
        # before convergence — adapt T to each preconditioner's C
        T = max(2, min(10, C // 3))
        fail_at = 2 * T
        for pp in (False, True):
            common = dict(strategy="esrp", T=T, phi=1, rtol=1e-8, chunk=32,
                          fail_at=fail_at, failed_nodes=[1], pff_precond=pp)
            solve_resilient(p, **common)             # warm the jit caches
            r = solve_resilient(p, **common)
            ev = r.events[0]
            row = dict(problem=kind, precond=name, pff_precond=pp, T=T,
                       fail_at=fail_at, iters=r.converged_iter,
                       recovery_ms=1e3 * r.recovery_s,
                       pff_iters=ev.pff_iters, inner_rel=r.inner_rel,
                       exact_rejoin=r.converged_iter == C)
            rows.append(row)
            lines.append(f"{kind},{name},{int(pp)},{T},{fail_at},"
                         f"{r.converged_iter},{row['recovery_ms']:.2f},"
                         f"{ev.pff_iters},{r.inner_rel:.2e},"
                         f"{int(row['exact_rejoin'])}")
            tag = "pff" if pp else "nopff"
            print(f"recovery_{name}_{tag},{1e3 * row['recovery_ms']:.0f},"
                  f"pff_iters={ev.pff_iters};"
                  f"exact={int(row['exact_rejoin'])}")
    for _, _, name in runs:
        sel = {r_["pff_precond"]: r_ for r_ in rows if r_["precond"] == name}
        if sel[False]["pff_iters"] > 0:
            speed = sel[False]["recovery_ms"] / max(sel[True]["recovery_ms"],
                                                    1e-9)
            it_cut = sel[False]["pff_iters"] / max(sel[True]["pff_iters"], 1)
            print(f"recovery_speedup_{name},0,"
                  f"wallclock={speed:.2f}x;pff_iter_cut={it_cut:.2f}x")
    assert all(r_["exact_rejoin"] for r_ in rows), "recovery lost exactness"
    _ensure_dir()
    with open("artifacts/bench/recovery.csv", "w") as f:
        f.write("\n".join(lines) + "\n")
    with open("artifacts/bench/BENCH_recovery.json", "w") as f:
        json.dump(dict(configs=[dict(kind=k, preconds=list(ps), **kw_)
                                for k, kw_, ps in configs],
                       n_nodes=8, rows=rows), f, indent=1, default=float)
    print(f"# wrote artifacts/bench/recovery.csv + BENCH_recovery.json "
          f"({len(rows)} rows)")


def bench_failures(full, sharded=False, tiers=False, trace=False):
    """Failure-scenario sweep: simultaneous vs staggered vs burst × φ × T
    for ESRP and IMCR — the multi-failure experiment of Pachajoa et al.
    (arXiv:1907.13077) on top of the paper's protocol.

      simultaneous  one event, φ nodes at once (worst case two iterations
                    before a storage stage completes)
      staggered     φ events of one node each, spaced a full period apart
                    (failure → recover → fail again)
      burst         two events one iteration apart: the second strikes the
                    re-run before the next storage stage completes, forcing
                    a rollback to the SAME reconstruction point again

    With ``--sharded`` (requires the 8-virtual-device XLA flag set by
    ``main``) the T=20 ESRP rows additionally run on the 8-device mesh with
    the device-resident failure runtime (redundancy copies physically on the
    neighbour devices, shard_map injection, recovery from surviving shards)
    and the ``sharded_iter``/``sharded_exact`` columns record the mesh run's
    convergence and whether it rejoined the single-device mesh-mirror
    trajectory bit-identically.

    Every row also carries ``tier_recovery_ms`` — the measured recovery
    time re-priced under each storage tier's read cost model (the fetch of
    the redundant p pair is the only tier-dependent step of a recovery).
    With ``tiers=True`` (``--tiers``) an additional tier × φ × T sweep runs
    REAL solves with ``storage_tier=...`` threaded through the driver, so
    the push/fetch accounting columns (push_count, push_bytes, model
    seconds) come from the solver itself, not a host-side re-pricing.

    Writes artifacts/bench/failures.csv (per-row sweep) and a
    machine-readable BENCH_failures.json next to it so the recovery-cost
    trajectory is trackable across PRs.

    All wall-clock rows are read back out of a span tracer (one
    ``measure:*`` span per timed solve), so the CSV columns and the
    exported trace can never disagree. With ``trace=True`` (``--trace``)
    the tracer is also threaded through every solve (per-iteration metrics
    ring, recovery spans) and exported to artifacts/obs/ as
    failures_trace.json (Chrome/Perfetto) + failures_events.jsonl +
    failures_metrics.txt. BENCH_failures.json always embeds the roofline
    FLOP/byte attribution of the dispatched kernels (CI fails without it).
    """
    import json

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core.driver import solve_resilient
    from repro.core.failures import FailureEvent
    from repro.core.tiers import TIERS, resolve_tier
    from repro.obs import (Tracer, metrics_snapshot, solver_rooflines,
                           write_chrome_trace, write_jsonl)
    from repro.sparse.matrices import build_problem

    tracer = Tracer("bench_failures")
    obs = tracer if trace else None
    n_nodes = 8
    kind, kw = "poisson2d", dict(nx=96 if full else 48)
    p = build_problem(kind, n_nodes=n_nodes, **kw)
    mesh = placed = sh_ops = mirror = frt = None
    if sharded:
        from repro.comm.shard import (ShardedFailureRuntime, mesh_mirror_ops,
                                      nodes_mesh, place_problem,
                                      sharded_solver_ops)
        if len(jax.devices()) < n_nodes:
            raise RuntimeError(
                f"--sharded needs {n_nodes} devices; run via main() so the "
                f"xla_force_host_platform_device_count flag is set before "
                f"jax imports")
        mesh = nodes_mesh(n_nodes)
        placed = place_problem(p, mesh)
        with mesh:
            sh_ops = sharded_solver_ops(placed, mesh)
        mirror = mesh_mirror_ops(p, n_nodes)
        # ONE runtime for the whole sweep: the jitted chunk runners key
        # their compile cache on its (per-phi cached) push closure, so a
        # fresh runtime per row would recompile every row; bind_plan resets
        # the per-solve wiped-copy tracking anyway
        frt = ShardedFailureRuntime(placed, mesh)

    def run_sharded(T, phi, events):
        """One mesh run + its mesh-mirror reference; returns the sharded
        column trio (iter, bit-exact rejoin, recovery ms)."""
        with mesh:
            r = solve_resilient(placed, strategy="esrp", T=T, phi=phi,
                                rtol=1e-8, chunk=32, scenario=list(events),
                                ops=sh_ops, failure_runtime=frt)
        rm = solve_resilient(p, strategy="esrp", T=T, phi=phi, rtol=1e-8,
                             chunk=32, scenario=list(events), ops=mirror)
        exact = bool((np.asarray(r.x) == np.asarray(rm.x)).all()
                     and r.converged_iter == rm.converged_iter)
        return r.converged_iter, exact, 1e3 * r.recovery_s
    with tracer.span("warmup:reference", cat="warmup"):
        solve_resilient(p, strategy="none", rtol=1e-8, chunk=32, obs=obs)
    with tracer.span("measure:reference", cat="measure") as ref_sp:
        ref = solve_resilient(p, strategy="none", rtol=1e-8, chunk=32,
                              obs=obs)
    C, t0 = ref.converged_iter, ref_sp.dur_s
    Ts = (10, 20, 50) if full else (10, 20)
    phis = (1, 2, 4) if full else (1, 2)

    def scenarios(T, phi):
        J1 = (C // 2 // T) * T + T - 2          # two before a stage completes
        spread = [(1 + 2 * i) % n_nodes for i in range(phi)]  # buddy-safe
        out = {"simultaneous": [FailureEvent(J1, tuple(spread))],
               "burst": [FailureEvent(J1, (1,)), FailureEvent(J1 + 1, (3,))]}
        if phi > 1:
            out["staggered"] = [FailureEvent(J1 + k * T, (spread[k],))
                                for k in range(phi)]
        return {name: evs for name, evs in out.items()
                if all(ev.iter < C for ev in evs)}

    header = ("strategy,T,phi,scenario,n_events,converged_iter,wasted_iters,"
              "recovery_ms,runtime_s,overhead_pct,rel_residual,drift,targets,"
              "sharded_iter,sharded_exact")
    lines = [header]
    rows = []
    for strategy in ("esrp", "imcr"):
        for T in Ts:
            for phi in phis:
                for scen, events in scenarios(T, phi).items():
                    # first run pays the one-off jit compiles of the
                    # post-failure chunk tails + reconstruction closures;
                    # report the warm second run (same policy as precond's
                    # us_per_iter note — compile time is not recovery cost)
                    label = f"{strategy}:{scen}:T{T}:phi{phi}"
                    with tracer.span(f"warmup:{label}", cat="warmup"):
                        solve_resilient(p, strategy=strategy, T=T, phi=phi,
                                        rtol=1e-8, chunk=32, scenario=events,
                                        obs=obs)
                    with tracer.span(f"measure:{label}",
                                     cat="measure") as m_sp:
                        r = solve_resilient(p, strategy=strategy, T=T,
                                            phi=phi, rtol=1e-8, chunk=32,
                                            scenario=events, obs=obs)
                    runtime_s = m_sp.dur_s
                    row = dict(
                        strategy=strategy, T=T, phi=phi, scenario=scen,
                        n_events=len(events),
                        event_iters=[e.iter for e in events],
                        converged_iter=r.converged_iter,
                        wasted_iters=r.wasted_iters,
                        recovery_ms=1e3 * r.recovery_s,
                        runtime_s=runtime_s,
                        overhead_pct=100 * (runtime_s - t0) / t0,
                        rel_residual=r.rel_residual, drift=r.drift,
                        targets=[e.target_iter for e in r.events],
                        per_event_wasted=[e.wasted_iters for e in r.events],
                        # the full schema-versioned report (NaN-free JSON;
                        # per-event recovery breakdown included)
                        report=r.to_json(),
                        # measured recovery re-priced per storage tier: the
                        # redundant-pair fetch is the tier-dependent step
                        tier_recovery_ms={
                            name: 1e3 * (r.recovery_s + sum(
                                t.read_s(e.fetch_bytes) for e in r.events
                                if e.fetch_bytes))
                            for name, t in TIERS.items()},
                        sharded_iter=None, sharded_exact=None,
                        sharded_recovery_ms=None)
                    if sharded and strategy == "esrp" and T == 20:
                        (row["sharded_iter"], row["sharded_exact"],
                         row["sharded_recovery_ms"]) = run_sharded(
                            T, phi, events)
                    rows.append(row)
                    si, se = row["sharded_iter"], row["sharded_exact"]
                    sh_cols = (f",{'' if si is None else si}"
                               f",{'' if se is None else int(se)}")
                    lines.append(
                        f"{strategy},{T},{phi},{scen},{len(events)},"
                        f"{r.converged_iter},{r.wasted_iters},"
                        f"{1e3 * r.recovery_s:.2f},{runtime_s:.3f},"
                        f"{row['overhead_pct']:.1f},{r.rel_residual:.2e},"
                        f"{r.drift:.2e},"
                        f"{'|'.join(str(t) for t in row['targets'])}"
                        + sh_cols)
    # --tiers: tier × φ × T with REAL per-tier solves (storage_tier threaded
    # through the driver) on the representative simultaneous ESRP scenario;
    # the data path is tier-independent, so converged_iter must match the
    # tier-less row and only the accounting columns move
    tier_rows = []
    if tiers:
        for T in Ts:
            for phi in phis:
                events = scenarios(T, phi)["simultaneous"]
                for name in TIERS:
                    with tracer.span(f"measure:tier:{name}:T{T}:phi{phi}",
                                     cat="measure"):
                        r = solve_resilient(p, strategy="esrp", T=T, phi=phi,
                                            rtol=1e-8, chunk=32,
                                            scenario=events,
                                            storage_tier=name, obs=obs)
                    t = resolve_tier(name)
                    tier_rows.append(dict(
                        tier=name, T=T, phi=phi, scenario="simultaneous",
                        converged_iter=r.converged_iter,
                        wasted_iters=r.wasted_iters,
                        recovery_ms=1e3 * r.recovery_s,
                        recovery_ms_model=1e3 * (r.recovery_s
                                                 + r.fetch_s_model),
                        push_count=r.push_count, push_bytes=r.push_bytes,
                        push_s_model=r.push_s_model,
                        fetch_bytes=sum(e.fetch_bytes for e in r.events),
                        fetch_s_model=r.fetch_s_model,
                        write_s_per_mb=t.write_s(1 << 20)))
        base = {(r_["T"], r_["phi"]): r_["converged_iter"] for r_ in rows
                if r_["strategy"] == "esrp"
                and r_["scenario"] == "simultaneous"}
        assert all(tr["converged_iter"] == base[(tr["T"], tr["phi"])]
                   for tr in tier_rows), "tier changed the data path"
        tier_header = ("tier,T,phi,converged_iter,recovery_ms,"
                       "recovery_ms_model,push_count,push_bytes,"
                       "push_s_model,fetch_bytes,fetch_s_model")
        tier_lines = [tier_header] + [
            f"{tr['tier']},{tr['T']},{tr['phi']},{tr['converged_iter']},"
            f"{tr['recovery_ms']:.2f},{tr['recovery_ms_model']:.2f},"
            f"{tr['push_count']},{tr['push_bytes']},"
            f"{tr['push_s_model']:.3e},{tr['fetch_bytes']},"
            f"{tr['fetch_s_model']:.3e}" for tr in tier_rows]
        _ensure_dir()
        with open("artifacts/bench/failures_tiers.csv", "w") as f:
            f.write("\n".join(tier_lines) + "\n")
        for tr in tier_rows:
            if tr["T"] == max(Ts) and tr["phi"] == max(phis):
                print(f"failures_tier_{tr['tier']}_T{tr['T']}"
                      f"_phi{tr['phi']},"
                      f"{1e3 * tr['recovery_ms_model']:.0f},"
                      f"push_bytes={tr['push_bytes']};"
                      f"push_s_model={tr['push_s_model']:.3e};"
                      f"fetch_s_model={tr['fetch_s_model']:.3e}")
    # harness CSV: the headline multi-failure settings at T=20
    for row in rows:
        if row["T"] == 20 and (row["phi"] == max(phis) or
                               row["scenario"] == "burst"):
            print(f"failures_{row['strategy']}_{row['scenario']}"
                  f"_T{row['T']}_phi{row['phi']},"
                  f"{1e6 * row['runtime_s']:.0f},"
                  f"wasted={row['wasted_iters']};"
                  f"recovery_ms={row['recovery_ms']:.2f};"
                  f"overhead_pct={row['overhead_pct']:.1f}")
    exact = sum(r_["converged_iter"] == C for r_ in rows)
    print(f"failures_exact_rejoin,0,rejoined={exact}/{len(rows)};ref_C={C}")
    sh_rows = [r_ for r_ in rows if r_["sharded_iter"] is not None]
    if sh_rows:
        ok = sum(bool(r_["sharded_exact"]) for r_ in sh_rows)
        worst = max(r_["sharded_recovery_ms"] for r_ in sh_rows)
        print(f"failures_sharded_rejoin,0,bit_exact={ok}/{len(sh_rows)};"
              f"max_recovery_ms={worst:.2f}")
    _ensure_dir()
    with open("artifacts/bench/failures.csv", "w") as f:
        f.write("\n".join(lines) + "\n")
    summary = dict(
        problem=dict(kind=kind, n_nodes=n_nodes, m=p.m, **kw),
        reference=dict(converged_iter=C, runtime_s=t0,
                       rel_residual=ref.rel_residual, drift=ref.drift,
                       report=ref.to_json()),
        # FLOP/byte attribution of the dispatched kernels from their lowered
        # HLO (repro.obs.rooflines) — the CI validator requires >= 3 priced
        # kernels here
        rooflines=solver_rooflines(p.solver_ops("auto"), p.b),
        sweep=dict(Ts=list(Ts), phis=list(phis),
                   strategies=["esrp", "imcr"]),
        rows=rows,
        tiers=dict(names=list(TIERS),
                   # constants provenance: "placeholder" class numbers or a
                   # scripts/calibrate_tiers.py measurement record (loaded
                   # via REPRO_TIER_CALIBRATION)
                   provenance={t.name: t.provenance
                               for t in TIERS.values()},
                   swept=bool(tier_rows), rows=tier_rows),
        aggregate=dict(
            n_rows=len(rows),
            exact_rejoin=exact,
            max_wasted_iters=max(r_["wasted_iters"] for r_ in rows),
            max_recovery_ms=max(r_["recovery_ms"] for r_ in rows),
            median_overhead_pct=float(np.median(
                [r_["overhead_pct"] for r_ in rows])),
            sharded_rows=len(sh_rows),
            sharded_bit_exact=sum(bool(r_["sharded_exact"])
                                  for r_ in sh_rows)))
    with open("artifacts/bench/BENCH_failures.json", "w") as f:
        json.dump(summary, f, indent=1, default=float)
    print(f"# wrote artifacts/bench/failures.csv + BENCH_failures.json "
          f"({len(rows)} rows)")
    if trace:
        os.makedirs("artifacts/obs", exist_ok=True)
        trace_path = write_chrome_trace(tracer,
                                        "artifacts/obs/failures_trace.json")
        jsonl_path = "artifacts/obs/failures_events.jsonl"
        if os.path.exists(jsonl_path):    # write_jsonl appends by design
            os.remove(jsonl_path)
        write_jsonl(tracer, jsonl_path)
        with open("artifacts/obs/failures_metrics.txt", "w") as f:
            f.write(metrics_snapshot(tracer))
        print(f"# wrote {trace_path} ({len(tracer.events)} events) "
              f"+ failures_events.jsonl + failures_metrics.txt")


def bench_serve(full, trace=False):
    """Streaming solver service: aggregate throughput + p50/p99 request
    latency vs micro-batch width B, with failures AND silent corruption
    injected under load, plus the deadline-aware front-end columns.

    The request stream is identical for every width (same seed, same RHS
    set) and ``fail_every=2`` lands the scenario — a FailureEvent *and* an
    SDCEvent — in every second micro-batch, so exactly half the requests
    ride through a failure + Alg. 2 recovery and an SDC detect→repair at
    *every* B (the per-request exposure is width-invariant and the
    comparison is fair). Each width gets one warmup pass covering both the
    failing and clean micro-batch compiles before the timed drain.

    A final pass at the widest B drives the deadline-aware policy
    (``max_queue_wait_s=0`` partial dispatches, per-request deadlines with
    a controlled set of pre-expired requests) — its row carries the
    queue-wait p99, deadline-miss rate, and partial-dispatch count, and the
    miss accounting must show ZERO requests mischaracterized as failures.

    Writes artifacts/bench/serve.csv + BENCH_serve.json; the JSON embeds
    the B>=8-vs-B=1 aggregate-throughput speedup (acceptance: > 2x) and the
    solver-kernel rooflines (the CI ``--min-kernels`` gate). With
    ``trace``, the widest sweep runs under an obs.Tracer and exports
    artifacts/obs/serve_trace.json + serve_metrics.txt."""
    import json

    import jax
    jax.config.update("jax_enable_x64", True)
    from repro.core.failures import FailureEvent, SDCEvent
    from repro.serve.solver_service import SolverService
    from repro.sparse.matrices import build_problem

    _ensure_dir()
    nx = 40 if full else 28
    n_req = 32 if full else 16
    widths = [1, 2, 4, 8, 16] if full else [1, 2, 4, 8]
    problem = build_problem("poisson2d", n_nodes=8, nx=nx)
    scenario = [FailureEvent(25, (1,)),
                SDCEvent(iter=38, nodes=(2,), target="r")]
    rng = np.random.default_rng(11)
    reqs = rng.standard_normal((n_req, problem.part.m))
    kw = dict(strategy="esrp", T=20, phi=1, rtol=1e-8)

    tracer = None
    if trace:
        from repro.obs import Tracer
        tracer = Tracer("bench_serve")
        tracer.meta.update(bench="serve", nx=nx, requests=n_req,
                           widths=widths)

    rows = []
    for B in widths:
        traced = trace and B == widths[-1]
        # warmup must compile the SAME chunk-runner variant the timed pass
        # dispatches: a tracer arms the metrics ring (a static argument of
        # the jitted runners), so the traced width warms under a throwaway
        # tracer or the timed drain would pay the recompile
        # B=1 runs the exact per-member bundle (the honest sequential
        # baseline — fused einsums only pay off once they amortize over
        # members); B>1 runs the fused throughput mode the service defaults
        # to. Warmup must compile the same variants.
        fused = B > 1
        warm = SolverService(problem, batch=B, scenario=scenario,
                             fail_every=1, obs=traced, fused=fused, **kw)
        for k in range(2 * B):            # one failing + one clean compile
            warm.submit(reqs[k % n_req])
        warm.run()
        svc = SolverService(problem, batch=B, scenario=scenario,
                            fail_every=2, obs=tracer if traced else None,
                            fused=fused, **kw)
        for k in range(n_req):
            svc.submit(reqs[k])
        svc.run()
        st = svc.stats()
        st["batch"] = B
        st["mode"] = "greedy"
        rows.append(st)
        us_per_req = st["solve_wall_s"] / st["requests"] * 1e6
        print(f"serve_B{B},{us_per_req:.0f},"
              f"rps={st['throughput_rps']:.2f};"
              f"p50_ms={st['latency_p50_ms']:.0f};"
              f"p99_ms={st['latency_p99_ms']:.0f};"
              f"converged={st['all_converged']}")

    # deadline-aware pass at the widest B: partial dispatches on queue-wait
    # timeout, generous live deadlines, and a controlled pair of pre-expired
    # requests — the miss accounting must never read as failures
    B = widths[-1]
    n_expired = 2
    svc = SolverService(problem, batch=B, scenario=scenario, fail_every=2,
                        fused=B > 1, max_queue_wait_s=0.0, **kw)
    for k in range(n_req):
        svc.submit(reqs[k], deadline_s=-1.0 if k < n_expired else 600.0)
        if (k + 1) % max(1, B // 2) == 0:   # below-width arrival bursts
            while svc.ready():
                svc.step()
    svc.run()
    st = svc.stats()
    st["batch"] = B
    st["mode"] = "deadline"
    rows.append(st)
    assert st["failed"] == 0, \
        f"deadline misses mischaracterized as failures: {st['failed']}"
    assert st["deadline_missed"] == n_expired, st["deadline_missed"]
    print(f"serve_deadline_B{B},partials={st['partial_dispatches']};"
          f"miss_rate={st['deadline_miss_rate']:.3f};"
          f"wait_p99_ms={st['queue_wait_p99_ms']:.1f};"
          f"failed={st['failed']}")

    thr = {r["batch"]: r["throughput_rps"] for r in rows
           if r["mode"] == "greedy"}
    wide = [b for b in thr if b >= 8]
    speedup = max(thr[b] for b in wide) / thr[1] if wide else float("nan")
    cols = ["mode", "batch", "requests", "microbatches", "mean_fill",
            "solve_wall_s", "throughput_rps", "latency_p50_ms",
            "latency_p99_ms", "latency_mean_ms", "queue_wait_p50_ms",
            "queue_wait_p99_ms", "deadline_miss_rate", "partial_dispatches",
            "retries_total", "failed", "iters_total", "all_converged"]
    with open("artifacts/bench/serve.csv", "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")
    from repro.obs import solver_rooflines
    with open("artifacts/bench/BENCH_serve.json", "w") as f:
        json.dump(dict(
            bench="serve", problem="poisson2d", nx=nx, n_nodes=8,
            requests=n_req, fail_every=2, scenario_iter=25, sdc_iter=38,
            rows=rows,
            deadline=dict(batch=B, expired_submitted=n_expired,
                          deadline_missed=st["deadline_missed"],
                          deadline_miss_rate=st["deadline_miss_rate"],
                          partial_dispatches=st["partial_dispatches"],
                          queue_wait_p99_ms=st["queue_wait_p99_ms"],
                          failed=st["failed"]),
            # solver-kernel FLOP/byte attribution (repro.obs.rooflines) —
            # the CI validator prices these with --min-kernels
            rooflines=solver_rooflines(problem.solver_ops("auto"),
                                       problem.b),
            speedup_b8_vs_b1=speedup,
            criteria=dict(metric="aggregate throughput at B>=8 vs B=1 "
                                 "sequential", threshold=2.0,
                          value=speedup, passed=bool(speedup > 2.0)),
        ), f, indent=1)
    print(f"# wrote artifacts/bench/serve.csv + BENCH_serve.json "
          f"(B>=8 vs B=1 speedup {speedup:.2f}x)")
    if tracer is not None:
        from repro.obs import metrics_snapshot, write_chrome_trace, \
            write_jsonl
        os.makedirs("artifacts/obs", exist_ok=True)
        path = write_chrome_trace(tracer, "artifacts/obs/serve_trace.json")
        jsonl_path = "artifacts/obs/serve_events.jsonl"
        if os.path.exists(jsonl_path):    # write_jsonl appends by design
            os.remove(jsonl_path)
        write_jsonl(tracer, jsonl_path)
        with open("artifacts/obs/serve_metrics.txt", "w") as f:
            f.write(metrics_snapshot(tracer))
        print(f"# wrote {path} + serve_events.jsonl + serve_metrics.txt")


ALL = {
    "table2": lambda full: bench_paper_table("table2", full),
    "table3": lambda full: bench_paper_table("table3", full),
    "table4": lambda full: bench_table4(full),
    "volume": lambda full: bench_volume(),
    "kernels": lambda full: bench_kernels(),
    "iteration": bench_iteration,
    "precond": bench_precond,
    "recovery": bench_recovery,
    "failures": bench_failures,
    "ft": lambda full: bench_ft(),          # --trace routed in main()
    "roofline": lambda full: bench_roofline(),
    "serve": bench_serve,                   # --trace routed in main()
}

# the --only list in the module docstring is derived from ALL so it cannot
# drift when benchmarks are added (it omitted "iteration" once already)
__doc__ = __doc__.replace("{only_choices}", "|".join(ALL))


def main() -> None:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, choices=list(ALL))
    ap.add_argument("--sharded", action="store_true",
                    help="failures sweep only: also run the T=20 ESRP rows "
                         "on an 8-device mesh with the device-resident "
                         "failure runtime (adds the sharded_iter/"
                         "sharded_exact columns)")
    ap.add_argument("--tiers", action="store_true",
                    help="failures sweep only: also run the storage-tier × "
                         "φ × T sweep with real per-tier solves "
                         "(storage_tier threaded through the driver); "
                         "writes failures_tiers.csv and the tiers section "
                         "of BENCH_failures.json")
    ap.add_argument("--trace", action="store_true",
                    help="failures/ft/serve sweeps: thread an obs.Tracer "
                         "through the solves and export Chrome-trace + "
                         "JSONL + metrics snapshot under artifacts/obs/")
    args = ap.parse_args()
    if args.sharded:
        # the 8-node mesh is emulated on host devices: pin this process to
        # the CPU so it never takes a chip. Must precede the first jax
        # import (bench functions import lazily).
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    names = [args.only] if args.only else list(ALL)
    for name in names:
        print(f"\n== {name} ==")
        if name == "failures":
            ALL[name](args.full, sharded=args.sharded, tiers=args.tiers,
                      trace=args.trace)
        elif name == "ft":
            bench_ft(trace=args.trace)
        elif name == "serve":
            bench_serve(args.full, trace=args.trace)
        else:
            ALL[name](args.full)


if __name__ == "__main__":
    main()
