"""Exact state reconstruction — paper Alg. 2, run on the replacement nodes.

Given the two latest redundantly-stored search directions p^(j-1), p^(j), the
replicated scalar β^(j-1), and the surviving parts of r^(j), x^(j), rebuild
the failed nodes' entries of z, r, x *exactly* (up to fp perturbation):

  line 4:  z_f = p_f^(j) − β^(j-1) p_f^(j-1)
  line 5:  v  = z_f − P_{f,I\f} r_{I\f}
  line 6:  solve P_ff r_f = v
  line 7:  w  = b_f − r_f − A_{f,I\f} x_{I\f}
  line 8:  solve A_ff x_f = w                     (inner PCG @ rtol 1e-14,
                                                   block-Jacobi precond — §5)

Lines 5-6 are *preconditioner-aware* (repro.precond): block-Jacobi keeps the
seed's exact closed forms (P offdiag ≡ 0 so v = z_f; P_ff⁻¹ = the raw
diagonal blocks so line 6 is a block matvec), while preconditioners with
genuine off-diagonal coupling (SSOR, Chebyshev, IC(0)) route through the
operators their class supplies: line 5 applies the actual P row strip to the
masked survivors, line 6 runs a real local P_ff solve whose operator
applications execute the preconditioner's kernels (triangular sweeps /
polynomial recurrence). The line-8 inner solve always uses block-Jacobi on
A_ff — a reconstruction-internal choice, independent of the hot-loop P.

Static data (A rows, P static state, b entries of the failed nodes) is
rebuilt from the problem's host-side COO — the paper's "retrieve from safe
storage".

The failed set may span several (non-contiguous) nodes — one simultaneous
multi-node event of the scenario engine resolves to ONE reconstruction over
the union I_f of all its failed rows (arXiv:1907.13077's simultaneous case);
the inner solves are zero-RHS-safe (``solve_pcg`` returns x = 0, rel = 0.0
instead of NaN when a strip of v or w is exactly zero).

Where p^(j-1), p^(j) come from is the caller's business: the single-device
simulator passes the host-visible queue slots, while the sharded runtime
assembles them from the *surviving devices'* physical queue shards
(``comm.shard.ShardedFailureRuntime.assemble_pair``) — ``reconstruct`` only
ever reads the failed rows of these vectors plus the surviving rows of
r, x, so either source yields the same algebra.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import failures
from repro.core.ops import SolverOps, bound, jacobi_apply, make_closure_ops
from repro.core.pcg import _expand, solve_pcg, solve_pcg_batched
from repro.obs.trace import span
from repro.sparse.blockell import BlockEll, ell_matvec
from repro.sparse.matrices import Problem


@dataclasses.dataclass
class ReconstructionOps:
    """Static per-(problem, failed-set) operators, built once per failure.

    In a real cluster this assembly happens on the replacement nodes from
    safe storage; it is therefore *not* part of the solver's dynamic state.
    """

    problem: Problem
    failed: list[int]
    mask: np.ndarray                 # (M,) bool over I_f
    f_rows: np.ndarray               # sorted global indices I_f
    a_rows_f: BlockEll               # A_{I_f, I}        (|I_f| x M strip)
    a_ff: BlockEll                   # A_{I_f, I_f}      (compact)
    diag_f: jax.Array                # (|I_f|/b, b, b) raw diag blocks (= P_ff^{-1})
    pinv_f: jax.Array                # (|I_f|/b, b, b) inverse blocks (A_ff precond)
    b_f: jax.Array
    inner: SolverOps = None          # line 8: A_ff with block-Jacobi pinv_f
    p_offdiag: object = None         # line 5: r_surv -> P_{f,I\f} r_{I\f}
    #                                  (None = exactly zero, block-Jacobi)
    p_solve: object = None           # line 6: v -> r_f solving P_ff r_f = v
    #                                  (None = seed diag-block matvec)

    @staticmethod
    def build(problem: Problem, failed: list[int],
              pff_precond: bool = True) -> "ReconstructionOps":
        part = problem.part
        failed = sorted(set(failed))
        mask = failures.failed_row_mask(part, failed)
        f_rows = failures.failed_rows(part, failed)
        to_compact = failures.compact_map(part, failed)
        # the compact strip is re-blocked at bm granularity below (rt = nf//bm
        # truncates); a misaligned union of failed rows would silently drop
        # rows instead of failing loudly — scenario events are validated
        # upstream, but ReconstructionOps is also a public entry point
        if f_rows.size % part.bm != 0:
            raise ValueError(
                f"failed-row union ({f_rows.size} rows) is not a multiple of "
                f"the block size bm={part.bm}")

        rows, cols, vals = problem.coo
        in_f_rows = mask[rows]
        # A_{I_f, I}: compact rows, global cols
        r_sel = rows[in_f_rows]
        a_rows_f = BlockEll.from_coo(
            to_compact(r_sel), cols[in_f_rows], vals[in_f_rows],
            m=part.m, bm=part.bm, bn=part.bn, dtype=np.asarray(vals).dtype)
        # from_coo builds square-shape metadata; fix the row extent
        nf = f_rows.size
        rt = nf // part.bm
        a_rows_f = BlockEll(a_rows_f.data[:rt], a_rows_f.idx[:rt],
                            a_rows_f.nblk[:rt], (nf, part.m), part.bm, part.bn)

        in_ff = in_f_rows & mask[cols]
        a_ff = BlockEll.from_coo(
            to_compact(rows[in_ff]), to_compact(cols[in_ff]), vals[in_ff],
            m=nf, bm=part.bm, bn=part.bn, dtype=np.asarray(vals).dtype)

        b_blk = problem.precond_block
        blk_ids = np.unique(f_rows // b_blk)
        pinv_f = problem.pinv_blocks[blk_ids]

        # recovery-aware lines 5-6: preconditioners with off-diagonal
        # coupling supply their own local operators; block-Jacobi (or a
        # legacy Problem without a precond object) keeps the seed shortcut
        # ``pff_precond`` threads to the line-6 inner CG: True runs it
        # preconditioned with the class's failed-slab-truncated operator
        # (precond.base._pff_inner_precond), False keeps the historical
        # unpreconditioned solve (the A/B the recovery microbench times)
        pc = problem.precond
        p_offdiag = p_solve = None
        if pc is not None and pc.name != "jacobi":
            p_offdiag, p_solve = pc.local_ops(mask, f_rows,
                                              pff_precond=pff_precond)

        return ReconstructionOps(
            problem=problem, failed=failed, mask=mask, f_rows=f_rows,
            a_rows_f=a_rows_f, a_ff=a_ff,
            diag_f=problem.diag_blocks[blk_ids],
            pinv_f=pinv_f,
            b_f=problem.b[f_rows],
            inner=bound(_inner_ops, (a_ff.data, a_ff.idx, pinv_f)),
            p_offdiag=p_offdiag, p_solve=p_solve)


def _inner_ops(operands) -> SolverOps:
    """``bind`` of the line-8 inner solve: A_ff's Block-ELL (data, idx)
    with the block-Jacobi inverse blocks of its rows. Module-level, so
    reconstructions of one failed-set size share the compiled solve."""
    data, idx, pinv = operands
    return make_closure_ops(lambda x: ell_matvec(data, idx, x),
                            lambda r: jacobi_apply(pinv, r))


def reconstruct(ops: ReconstructionOps, *, p_prev: jax.Array, p_curr: jax.Array,
                beta_prev: jax.Array, r_surv: jax.Array, x_surv: jax.Array,
                inner_rtol: float | None = None,
                inner_max_iters: int = 20_000,
                b_f: jax.Array | None = None, tracer=None):
    """Run Alg. 2. Inputs are full-length vectors; only surviving (resp.
    redundant-copy) entries are read, enforced by masking. Returns the failed
    nodes' compact (x_f, r_f, z_f), the line-8 inner solve's relative
    residual and its iteration count (device scalars).

    Each Alg. 2 phase is an ``obs.span`` — lines 4-5 (off-diagonal apply),
    the line-6 P_ff solve, the line-7 RHS assembly, the line-8 A_ff solve.
    Per-phase wall times are only meaningful with a host sync at each
    boundary, so with a ``tracer`` (an obs.Tracer) the spans
    block_until_ready their segment's outputs; with tracer=None nothing
    syncs and the spans time the host's dispatch (the default async hot
    path is untouched).

    Batch-polymorphic: (B, M) vector inputs with (B,) ``beta_prev`` run ONE
    Alg. 2 pass over the shared f-slab for all B members — the static strip
    operators are shared, lines 4/6/7 apply the per-member-unrolled scalar
    subgraphs, and line 8 is a single batched inner solve with per-member
    freeze (``run_pcg_batched``) — so each member's reconstruction is
    bit-identical in f64 to its own B=1 pass. Batched mode requires the
    block-Jacobi closed forms (``p_solve is None``).

    ``b_f`` overrides the RHS entries baked into ``ops`` (line 7) — the
    batched driver solves B different right-hand sides against the one
    static strip set, so it passes ``rhs[:, f_rows]`` here.

    ``inner_rtol`` defaults to the paper's 1e-14 where the dtype can reach
    it and to ten machine epsilons where it cannot (1.2e-6 in float32), so
    an f32 inner solve stops at its floor instead of running out its
    iteration budget.
    """
    if inner_rtol is None:
        inner_rtol = max(1e-14, 10 * float(jnp.finfo(r_surv.dtype).eps))
    sync = jax.block_until_ready if tracer is not None else (lambda x: x)
    mask = jnp.asarray(ops.mask)
    f_rows = jnp.asarray(ops.f_rows)
    b = ops.problem.precond_block
    batched = x_surv.ndim == 2
    if batched and ops.p_solve is not None:
        raise NotImplementedError(
            "batched reconstruction supports the block-Jacobi closed forms "
            "only (preconditioners with off-diagonal coupling pend)")

    itemsize = np.dtype(r_surv.dtype).itemsize
    with span("alg2_line5_offdiag", "recovery", tracer,
              n_failed_rows=int(ops.f_rows.size),
              bytes=int((ops.f_rows.size + r_surv.size) * itemsize),
              jacobi_closed_form=ops.p_solve is None):
        p_prev_f = p_prev[..., f_rows]
        p_curr_f = p_curr[..., f_rows]
        z_f = p_curr_f - _expand(beta_prev, p_curr_f) * p_prev_f  # line 4
        if ops.p_solve is None:
            # block-Jacobi closed form: P_{f,I\f} == 0, so line 5 is v = z_f
            v = sync(z_f)                                       # line 5
        else:
            # genuine off-diagonal coupling: apply the real P row strip to
            # the surviving entries (the closure masks I_f)
            v = sync(z_f - ops.p_offdiag(r_surv))               # line 5

    with span("alg2_line6_pff_solve", "recovery", tracer,
              jacobi_closed_form=ops.p_solve is None) as sp6:
        if ops.p_solve is None:
            # block-Jacobi closed form: P_ff^{-1} = A_bb, one block matvec
            # (per member when batched — keeps the scalar subgraph exact)
            def pff_mv(vi):
                return jnp.einsum("nij,nj->ni", ops.diag_f,
                                  vi.reshape(-1, b)).reshape(-1)
            r_f = sync(pff_mv(v) if not batched else
                       jnp.stack([pff_mv(v[i])
                                  for i in range(v.shape[0])]))   # line 6
        else:
            # real local P_ff solve through the preconditioner's kernels
            r_f = sync(ops.p_solve(v, inner_rtol, inner_max_iters))  # line 6
            stats = getattr(ops.p_solve, "stats", None)
            if tracer is not None and stats:
                sp6.args.update({k: jsonable_stat(v2)
                                 for k, v2 in dict(stats).items()})

    with span("alg2_line7_w", "recovery", tracer):
        x_masked = jnp.where(mask, jnp.zeros_like(x_surv), x_surv)
        if not batched:
            ax = ops.a_rows_f.matvec(x_masked)
        else:
            ax = jnp.stack([ops.a_rows_f.matvec(x_masked[i])
                            for i in range(x_masked.shape[0])])
        w = sync((ops.b_f if b_f is None else b_f) - r_f - ax)     # line 7

    with span("alg2_line8_aff_solve", "recovery", tracer,
              inner_rtol=inner_rtol) as sp8:
        solve = solve_pcg if not batched else solve_pcg_batched
        state, rel = solve(ops.inner, w, inner_rtol,
                           inner_max_iters)                        # line 8
        x_f = sync(state.x)
        if tracer is not None:
            sp8.args["inner_rel"] = (float(rel) if not batched
                                     else float(np.max(np.asarray(rel))))
            sp8.args["inner_iters"] = int(state.j)
    return x_f, r_f, z_f, rel, state.j


def jsonable_stat(v):
    """Best-effort scalar coercion for p_solve iteration stats."""
    try:
        return float(np.asarray(v).reshape(()))
    except Exception:                                  # noqa: BLE001
        return str(v)


def scatter_failed(full_surv: jax.Array, compact_f: jax.Array,
                   ops: ReconstructionOps) -> jax.Array:
    """Merge reconstructed failed entries into the surviving vector.
    Batch-polymorphic: (B, M) + (B, |I_f|) scatters per member."""
    return full_surv.at[..., jnp.asarray(ops.f_rows)].set(compact_f)
