"""SolverOps execution layer: cross-backend trajectory bit-identity, cond
gating of the storage/replacement bookkeeping, and the driver's sync-free
convergence protocol.

The load-bearing property: the Pallas-backed bundle (interpret mode on CI)
must be *bit-identical* in f64 to the jnp reference bundle — iteration by
iteration, through storage stages and a mid-stage failure/recovery — so the
kernels can be swapped into the paper's experiments without perturbing the
trajectory-identity argument.
"""
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests._hypo import given, settings, st

from repro.analysis import walker
from repro.core import esrp
from repro.core.driver import solve_resilient
from repro.core.ops import make_closure_ops
from repro.kernels.fused_pcg.fused_pcg import (ROW_TARGET, lanes_for,
                                               update_rows)
from repro.sparse.matrices import build_problem


@pytest.fixture(scope="module")
def problems():
    return {
        "poisson2d": build_problem("poisson2d", n_nodes=4, nx=16, ny=16),
        "poisson3d": build_problem("poisson3d", n_nodes=4, nx=8),
    }


def _assert_tree_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# --------------------------------------------------------------------------- #
# cross-backend bit-identity
# --------------------------------------------------------------------------- #
@settings(max_examples=6, deadline=None)
@given(kind=st.sampled_from(["poisson2d", "poisson3d"]),
       T=st.sampled_from([1, 20]), n_iters=st.integers(10, 30))
def test_trajectory_bit_identical_across_backends(problems, kind, T, n_iters):
    p = problems[kind]
    ops_jnp = p.solver_ops("jnp")
    ops_pal = p.solver_ops("interpret")
    thresh = jnp.asarray(0.0, p.b.dtype)

    s_j = esrp.esrp_init(ops_jnp.matvec, ops_jnp.precond, p.b)
    s_p = esrp.esrp_init(ops_pal.matvec, ops_pal.precond, p.b)
    _assert_tree_equal(s_j, s_p)
    s_j, norms_j = esrp.run_chunk(s_j, ops_jnp, T, n_iters, thresh)
    s_p, norms_p = esrp.run_chunk(s_p, ops_pal, T, n_iters, thresh)
    np.testing.assert_array_equal(np.asarray(norms_j), np.asarray(norms_p))
    _assert_tree_equal(s_j, s_p)


def test_failure_recovery_bit_identical_across_backends(problems):
    """Mid-stage failure (right after the first push of a stage) + Alg. 2
    reconstruction must leave both backends on the same bit-exact state."""
    p = problems["poisson2d"]
    ref = solve_resilient(p, strategy="none", rtol=1e-9, backend="jnp")
    reports = {}
    for backend in ("jnp", "interpret"):
        reports[backend] = solve_resilient(
            p, strategy="esrp", T=5, phi=1, rtol=1e-9, chunk=16,
            fail_at=15, failed_nodes=[2], backend=backend)
    rj, rp = reports["jnp"], reports["interpret"]
    assert rj.converged_iter == rp.converged_iter == ref.converged_iter
    assert rj.rel_residual == rp.rel_residual
    assert rj.target_iter == rp.target_iter
    assert rj.rel_residual < 1e-9


@pytest.mark.parametrize("batch", [0, 2])
@pytest.mark.parametrize("backend", ["jnp", "interpret"])
def test_chunk_runner_takes_operands_as_arguments(problems, backend, batch):
    """The bundle's arrays reach the compiled chunk runner as arguments:
    closed over, they would be embedded in the program as constants (5.4
    GB of literal at 128³)."""
    p = problems["poisson3d"]
    ops = p.solver_ops(backend, batch=batch)
    rhs = p.b if not batch else jnp.stack([p.b] * batch)
    st = esrp.esrp_init(ops.matvec, ops.precond, rhs, dot=ops.dot)
    lowered = esrp.run_chunk.lower(st, ops, 5, 4, None)
    shapes = [a.shape for a in jax.tree.leaves(lowered.args_info)]
    for arr in ops.operands:
        assert arr.shape in shapes
    # no array constant over 1 KiB (2048 hex digits) in the program
    literals = re.findall(r'dense<"0x([0-9A-F]*)"', lowered.as_text())
    assert max(map(len, literals), default=0) < 2048


def test_closure_ops_match_seed_numerics(problems):
    """The closure bundle (arbitrary matvec/precond) reproduces the seed's
    unfused op order: solving through it must be bit-identical to the jnp
    einsum path it wraps."""
    p = problems["poisson2d"]
    ops = make_closure_ops(p.a.matvec, p.apply_precond)
    thresh = jnp.asarray(0.0, p.b.dtype)
    s = esrp.esrp_init(ops.matvec, ops.precond, p.b)
    s, norms = esrp.run_chunk(s, ops, 20, 20, thresh)
    # independent replay of Alg. 1 in the seed op order
    x = jnp.zeros_like(p.b)
    r = p.b - p.a.matvec(x)
    z = p.apply_precond(r)
    pv, rz = z, r @ z
    for _ in range(20):
        q = p.a.matvec(pv)
        alpha = rz / (pv @ q)
        x = x + alpha * pv
        r = r - alpha * q
        z = p.apply_precond(r)
        rz_new = r @ z
        pv = z + (rz_new / rz) * pv
        rz = rz_new
    # eager replay vs jitted scan: same op order, but XLA fuses FMA inside
    # the jit — compare to fp noise, not bitwise
    np.testing.assert_allclose(np.asarray(s.pcg.x), np.asarray(x),
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(s.pcg.r), np.asarray(r),
                               rtol=1e-10, atol=1e-12)


# --------------------------------------------------------------------------- #
# cond gating (traversal shared with the static analyzer: repro.analysis)
# --------------------------------------------------------------------------- #
def _dots(jaxpr):
    """Count dot_general eqns executed unconditionally: recurses through
    sub-jaxprs (pjit bodies etc.) but NOT into cond branches."""
    return walker.count_primitives(jaxpr, "dot_general", into_conds=False)


def test_cond_gates_storage_and_replacement(problems):
    """gated=True must hoist the queue rotation, star capture, and the
    residual-replacement SpMV+precond into lax.cond branches: no extra
    SpMV/dot executes on non-replacement iterations. gated=False (the seed
    path) keeps them inline in the main trace."""
    p = problems["poisson2d"]
    ops = p.solver_ops("jnp")
    s0 = esrp.esrp_init(ops.matvec, ops.precond, p.b)

    def step(gated):
        return jax.make_jaxpr(
            lambda s: esrp.esrp_step(s, ops, 20, b=p.b, rr_every=10,
                                     gated=gated))(s0).jaxpr

    gated, ungated = step(True), step(False)
    conds = [e for e in gated.eqns if e.primitive.name == "cond"]
    assert len(conds) >= 3          # queue push, star capture, replacement
    # inline (unconditionally executed) dots: gated must have strictly fewer
    # — the replacement SpMV (kmax dots) + precond dot moved under cond
    top_gated = _dots(gated)
    top_ungated = _dots(ungated)
    assert top_gated < top_ungated, (top_gated, top_ungated)
    kmax = p.a.kmax
    # the replacement branch is one SpMV (kmax slot dots) + precond einsum
    # + the rᵀz dot — all inline when ungated, all under cond when gated
    assert top_ungated - top_gated == kmax + 2, (top_gated, top_ungated)


def test_gated_trajectory_matches_ungated(problems):
    """cond-gating is a pure execution change: jnp.where-selected and
    cond-branched bookkeeping must produce bit-identical trajectories."""
    p = problems["poisson2d"]
    ops = p.solver_ops("jnp")
    thresh = jnp.asarray(0.0, p.b.dtype)
    out = {}
    for gated in (True, False):
        s = esrp.esrp_init(ops.matvec, ops.precond, p.b)
        s, norms = esrp.run_chunk(s, ops, 5, 25, thresh, 8, gated, p.b)
        out[gated] = (s, norms)
    np.testing.assert_array_equal(np.asarray(out[True][1]),
                                  np.asarray(out[False][1]))
    _assert_tree_equal(out[True][0], out[False][0])


# --------------------------------------------------------------------------- #
# driver protocol
# --------------------------------------------------------------------------- #
def test_driver_never_reruns_final_chunk(problems):
    """The convergence freeze makes each chunk dispatch exactly once: the
    number of run() invocations is the chunk count needed to cover the
    converged iteration — not one extra for the re-run tail."""
    p = problems["poisson2d"]
    for chunk in (16, 64):
        r = solve_resilient(p, strategy="none", rtol=1e-9, chunk=chunk)
        # seed protocol used ceil(C/chunk) + 1 (tail re-run); the overlap
        # protocol may dispatch at most one speculative chunk past
        # convergence, and never re-runs.
        needed = math.ceil(r.converged_iter / chunk)
        assert needed <= r.run_calls <= needed + 1, (r.run_calls, needed)
        assert r.rel_residual < 1e-9


def test_driver_report_consistent_with_and_without_failure(problems):
    p = problems["poisson2d"]
    ref = solve_resilient(p, strategy="none", rtol=1e-9, chunk=32)
    r = solve_resilient(p, strategy="esrp", T=5, phi=1, rtol=1e-9, chunk=32,
                        fail_at=max(4, ref.converged_iter // 2),
                        failed_nodes=[1])
    assert r.converged_iter == ref.converged_iter
    assert r.rel_residual < 1e-9


def test_update_rows_divides():
    for m, b in ((320, 10), (1280, 10), (1024, 4), (512, 8), (128 ** 3, 8)):
        rows = update_rows(m, b)
        lanes = lanes_for(rows, b)
        assert m % rows == 0 and rows % lanes == 0 and lanes % b == 0
        assert (rows <= ROW_TARGET and (rows // lanes) % 8 == 0) \
            or rows == m
