"""SolverOps — the execution layer of the PCG/ESRP/IMCR hot loop.

The paper's resilience argument (and Levonyak et al.'s scaling argument for
resilient PCG) only holds if the failure-free iteration runs as fast as the
hardware allows; constant-factor slack in the hot loop gets misread as
resilience overhead. ``SolverOps`` bundles the four operations one PCG
iteration needs —

  * ``matvec``      q = A·p                       (the SpMV)
  * ``matvec_dot``  (q, pᵀq) in one pass          (α needs no 2nd read of p/q)
  * ``precond``     z = P r                       (block-Jacobi apply)
  * ``update``      (x', r', z', rz') fused       (Alg. 1 lines 4-7, one pass)

— so the solver core is written once against the bundle and the backend
decides how each op executes:

  * ``jnp``       reference backend: pure-jnp ops *structured like the
                  kernels* (sequential k accumulation, per-block partial
                  dots), so its f64 trajectory is bit-identical to the
                  Pallas one — the cross-backend trajectory-identity
                  property tested in tests/test_solver_ops.py.
  * ``pallas``    the TPU kernels (kernels/spmv, kernels/fused_pcg).
  * ``interpret`` the same kernels in Pallas interpret mode (CI validation).
  * closures      arbitrary (matvec, precond) pairs — dense test operators,
                  user matvecs, the reconstruction inner solve — via
                  ``make_closure_ops``; numerics identical to the seed path.

A bundle is a pytree. Its leaves are ``operands``, the arrays its ops read
(the matrix's storage, the preconditioner); ``bind`` rebuilds the ops over
whatever leaves it is given. The jitted chunk runners take the bundle as an
ordinary argument, so the operands reach the compiled program as arguments
and the ops are rebound to their tracers. Ops that closed over the arrays
instead would embed them in every program as constants: at 128³ that is
5.4 GB of literal, which lowering copies several times over in host
memory. A closure bundle (``make_closure_ops``) has no operands; its
treedef is the bundle itself, keyed on the functions' identities, as a
static argument would be. Bundles are cached per (problem, backend), and
equal ``bind``s share their compiled programs.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax


class SolverOps(NamedTuple):
    """Execution backend for one PCG iteration; a pytree over
    ``operands`` (see the module docstring)."""
    backend: str
    matvec: Callable            # p -> q = A p
    matvec_dot: Callable        # p -> (q, p @ q)
    precond: Callable           # r -> z = P r
    update: Callable            # (alpha, x, r, p, q) -> (x', r', z', rz')
    variant: str = ""           # preconditioner execution variant (e.g. the
    #                             sharded runtime's "node-local ssor")
    dot: Callable | None = None  # (u, v) -> uᵀv with this backend's reduction
    #                             structure; None = plain u @ v. Off-hot-loop
    #                             dots (pcg_init's r₀ᵀz₀, the residual-
    #                             replacement rᵀz) route through it so the
    #                             sharded runtime and its single-device
    #                             mesh-mirror stay bit-identical in f64.
    operands: tuple = ()        # arrays the ops read: the pytree's leaves
    bind: Callable | None = None  # operands -> bundle whose ops read them;
    #                               None = a closure bundle, no operands
    spmv_format: str = ""       # the SpMV's storage: "dia" | "blockell";
    #                             "" for a closure bundle
    spmv_diagonals: int = 0     # diagonals stored ("dia" only)


def bound(bind: Callable, operands) -> SolverOps:
    """The bundle ``bind(operands)``, recording both so that a jitted
    function taking it passes ``operands`` as arguments. ``bind`` must only
    build closures, since it also runs on tracers and placeholders, and
    must close over no array. Give it value equality (a frozen dataclass)
    to share compiled programs between bundles of one configuration."""
    operands = tuple(operands)
    return bind(operands)._replace(operands=operands, bind=bind)


def _flatten(ops: SolverOps):
    if ops.bind is None:
        return (), ops
    return ops.operands, ops.bind


def _unflatten(aux, leaves):
    if isinstance(aux, SolverOps):
        return aux
    return bound(aux, leaves)


jax.tree_util.register_pytree_node(SolverOps, _flatten, _unflatten)


def make_closure_ops(matvec: Callable, precond: Callable) -> SolverOps:
    """Wrap arbitrary (matvec, precond) closures. The update is the seed's
    unfused op sequence, so trajectories through this bundle are bit-identical
    to the pre-SolverOps code path. Callers that solve repeatedly should hold
    on to the returned bundle (the driver caches it on the Problem): the
    jitted chunk runners key their compile cache on it."""

    def matvec_dot(p):
        q = matvec(p)
        return q, p @ q

    def update(alpha, x, r, p, q):
        x_new = x + alpha * p
        r_new = r - alpha * q
        z_new = precond(r_new)
        return x_new, r_new, z_new, r_new @ z_new

    return SolverOps("closure", matvec, matvec_dot, precond, update)


def batch_ops(ops: SolverOps, nbatch: int) -> SolverOps:
    """Per-member-unrolled batched bundle over an arbitrary SolverOps.

    Every op applies the wrapped bundle's exact unbatched subgraph to each
    member row and stacks — member i of the batched trajectory is
    bit-identical in f64 to a B=1 run through ``ops`` (a fused batched
    einsum or ``jax.vmap`` is *not*: XLA reassociates the contractions).
    Used for closure/non-Jacobi bundles and the Alg. 2 batched inner solves;
    the Jacobi problem bundles get genuinely batched kernels instead
    (``make_problem_ops(batch=...)``) with the same per-member guarantee.
    A bundle with operands stays one: the batched bundle reads the same."""
    if ops.bind is not None:
        return bound(_PerMember(ops.bind, nbatch), ops.operands)
    return _per_member(ops, nbatch)


@dataclasses.dataclass(frozen=True)
class _PerMember:
    """``bind`` of ``batch_ops`` over a bundle with operands."""
    inner: Callable
    nbatch: int

    def __call__(self, operands):
        return _per_member(self.inner(operands), self.nbatch)


def _per_member(ops: SolverOps, nbatch: int) -> SolverOps:
    import jax.numpy as jnp

    def member_dot(u, v):
        return (u @ v) if ops.dot is None else ops.dot(u, v)

    def matvec(p):
        return jnp.stack([ops.matvec(p[i]) for i in range(nbatch)])

    def matvec_dot(p):
        pairs = [ops.matvec_dot(p[i]) for i in range(nbatch)]
        return (jnp.stack([q for q, _ in pairs]),
                jnp.stack([d for _, d in pairs]))

    def precond(r):
        return jnp.stack([ops.precond(r[i]) for i in range(nbatch)])

    def update(alpha, x, r, p, q):
        outs = [ops.update(alpha[i], x[i], r[i], p[i], q[i])
                for i in range(nbatch)]
        return tuple(jnp.stack([o[j] for o in outs]) for j in range(4))

    def dot(u, v):
        return jnp.stack([member_dot(u[i], v[i]) for i in range(nbatch)])

    return SolverOps(ops.backend, matvec, matvec_dot, precond, update,
                     ops.variant, dot, spmv_format=ops.spmv_format,
                     spmv_diagonals=ops.spmv_diagonals)


def jacobi_apply(pinv_blocks, r):
    """z = P r for block-Jacobi, as the seed's einsum over the (M/b, b, b)
    inverse blocks: the bundles' standalone precond and
    ``Problem.apply_precond`` both run this one expression."""
    import jax.numpy as jnp

    b = pinv_blocks.shape[-1]
    return jnp.einsum("nij,nj->ni", pinv_blocks,
                      r.reshape(-1, b)).reshape(-1)


def make_problem_ops(problem, backend: str, batch: int = 0,
                     fused: bool = False) -> SolverOps:
    """SolverOps over a ``Problem``'s operator and its registered
    preconditioner. backend: "jnp" | "pallas" | "interpret".

    The SpMV runs on the storage ``problem_spmv`` picks from the operator:
    by diagonals (``kernels/spmv/dia``) when it has few, else Block-ELL
    (``kernels/spmv/spmv``).

    Block-Jacobi (the default) keeps the fully fused update path — the
    preconditioner apply happens *inside* ``kernels/fused_pcg`` while r' is
    still in VMEM. Other preconditioners (SSOR, Chebyshev, IC(0)) cannot fuse
    into that kernel: the update is the x/r axpy pair + the preconditioner's
    own backend-routed apply + a plain rᵀz dot, written once in shared jnp so
    cross-backend bit-identity reduces to the apply's bit-identity (tested
    per preconditioner in tests/test_precond.py).

    ``batch`` > 0 builds the batched bundle: every op takes/returns a leading
    B axis ((B, M) vectors, (B,) scalars) and one dispatch advances all B
    members. Jacobi routes through the genuinely batched kernels (leading-B
    grid dim / per-member-unrolled refs), so member i stays bit-identical in
    f64 to its B=1 run on the same backend; other preconditioners fall back
    to the generic per-member wrapper ``batch_ops``.

    ``fused=True`` (batched Jacobi only) swaps the jnp hot-loop ops for the
    fused-batched variants — one op serves all B members, which is
    what amortizes the batch on an op-overhead-bound host backend — at the
    price of per-member rounding no longer being bit-identical to the B=1
    run (~ulp deviation; convergence unaffected). The serving path opts in;
    the default stays exact.

    The bundle's operands are the matrix's (the diagonals, or Block-ELL's
    data and column indices), and for block-Jacobi its diagonals
    (``jacobi_diagonals``, what the fused update reads) and inverse blocks
    (the standalone precond). A non-Jacobi preconditioner's apply keeps its
    own arrays."""
    from repro.kernels.fused_pcg.fused_pcg import (jacobi_diagonals,
                                                   lanes_for, update_rows)

    if backend not in ("jnp", "pallas", "interpret"):
        raise ValueError(f"unknown SolverOps backend {backend!r}")
    layout, mat = problem_spmv(problem, backend)
    jacobi = problem.precond is None or problem.precond.name == "jacobi"
    if not jacobi:
        base = bound(_ProblemOps(backend, layout,
                                 problem.precond.make_apply(backend)), mat)
        # non-jacobi batched bundles stay per-member-unrolled even under
        # fused: the sweep/polynomial applies have no fused-batched form
        return batch_ops(base, batch) if batch else base
    rows = update_rows(problem.m, problem.precond_block)
    dia = jacobi_diagonals(problem.pinv_blocks,
                           lanes_for(rows, problem.precond_block))
    return bound(_ProblemOps(backend, layout, rows=rows, batch=batch,
                             fused=fused),
                 (*mat, dia, problem.pinv_blocks))


def problem_spmv(problem, backend: str) -> tuple:
    """(layout, arrays) of the problem's SpMV storage for ``backend``:
    the diagonal kernel's ``DiaLayout`` and the diagonals, or None and
    Block-ELL's data and column indices. Chosen from the operator and
    cached on the problem. Diagonals when both hold: they store fewer
    values than the Block-ELL tiles (n_diag · M < rt · kmax · bm · bn), and
    the diagonal kernel has a layout for the offsets (few enough of them,
    an x window that fits VMEM and, compiled for the chip, blocks that
    Mosaic takes); else Block-ELL."""
    mosaic = backend == "pallas"
    cache = getattr(problem, "_spmv_cache", None)
    if cache is None:
        cache = problem._spmv_cache = {}
    if mosaic not in cache:
        cache[mosaic] = _choose_spmv(problem, mosaic, cache)
    return cache[mosaic]


def _choose_spmv(problem, mosaic: bool, cache: dict) -> tuple:
    import numpy as np

    from repro.kernels.spmv.dia import dia_layout
    from repro.sparse.dia import Diagonals, dia_lanes, offsets_of

    a, m = problem.a, problem.m
    rows, cols, vals = problem.coo
    offsets = offsets_of(rows, cols, m)
    lanes = dia_lanes(m)
    layout = dia_layout(m // lanes, lanes, offsets,
                        np.dtype(a.dtype).itemsize, mosaic=mosaic)
    if layout is None or offsets.size * m >= a.data.size:
        return None, (a.data, a.idx)
    if "diagonals" not in cache:     # one copy for every backend's layout
        cache["diagonals"] = Diagonals.from_coo(rows, cols, vals, m,
                                                a.dtype).data
    return layout, (cache["diagonals"],)


@dataclasses.dataclass(frozen=True)
class _ProblemOps:
    """``bind`` of the problem bundles: the ops of ``backend`` over the
    matrix's operands (the diagonals at ``layout``, or with ``layout``
    None Block-ELL's data and indices) then, for block-Jacobi, (dia, pinv).
    ``precond`` is a non-Jacobi apply; without it the bundle is
    block-Jacobi with ``rows`` elements per update step."""
    backend: str
    layout: object = None       # kernels.spmv.dia.DiaLayout
    precond: Callable | None = None
    rows: int = 0
    batch: int = 0
    fused: bool = False

    def __call__(self, operands):
        n = 2 if self.layout is None else 1
        mat, rest = tuple(operands[:n]), operands[n:]
        if self.precond is not None:
            ops = _swept_ops(self.backend, self.layout, self.precond, mat)
        elif self.batch:
            ops = _batched_jacobi_ops(self, mat, *rest)
        else:
            ops = _jacobi_ops(self.backend, self.layout, self.rows, mat,
                              *rest)
        if self.layout is None:
            return ops._replace(spmv_format="blockell")
        return ops._replace(spmv_format="dia",
                            spmv_diagonals=len(self.layout.offsets))


def _spmv_pair(backend: str, layout, mat: tuple, batch: int = 0,
               fused: bool = False):
    """(matvec, matvec_dot) over the matrix operands ``mat`` (by diagonals
    at ``layout``, or Block-ELL where it is None), of one vector or, with
    ``batch``, of a (B, M) batch: one kernel call for all members, or on the
    jnp backend the per-member-unrolled refs (exact) or, ``fused``, the
    whole-batch refs (~ulp)."""
    from repro.kernels.spmv import dia, ref
    from repro.kernels.spmv import spmv as block_ell

    if backend == "jnp":
        mode = "fused" if fused else "batched" if batch else "one"
        kw = {}
        pairs = {
            (True, "one"): (ref.spmv_dia_ref, ref.spmv_dia_dot_ref),
            (True, "batched"): (ref.spmv_dia_ref_batched,
                                ref.spmv_dia_dot_ref_batched),
            (True, "fused"): (ref.spmv_dia_ref, ref.spmv_dia_dot_ref_fused),
            (False, "one"): (ref.spmv_seq_ref, ref.spmv_dot_ref),
            (False, "batched"): (ref.spmv_seq_ref_batched,
                                 ref.spmv_dot_ref_batched),
            (False, "fused"): (ref.spmv_seq_ref_fused,
                               ref.spmv_dot_ref_fused),
        }
    else:
        mode = "batched" if batch else "one"
        kw = {"interpret": backend == "interpret"}
        pairs = {
            (True, "one"): (dia.spmv_dia, dia.spmv_dia_dot),
            (True, "batched"): (dia.spmv_dia_batched,
                                dia.spmv_dia_dot_batched),
            (False, "one"): (block_ell.spmv, block_ell.spmv_dot),
            (False, "batched"): (block_ell.spmv_batched,
                                 block_ell.spmv_dot_batched),
        }
    if layout is not None:
        kw["layout"] = layout
    mv, mv_dot = pairs[layout is not None, mode]
    return lambda x: mv(*mat, x, **kw), lambda x: mv_dot(*mat, x, **kw)


def _jacobi_ops(backend, layout, rows, mat, dia, pinv) -> SolverOps:
    from repro.kernels.fused_pcg.fused_pcg import fused_pcg_update
    from repro.kernels.fused_pcg.ref import fused_pcg_update_seq_ref

    mv, mv_dot = _spmv_pair(backend, layout, mat)
    if backend == "jnp":
        def update(alpha, x, r, p, q):
            return fused_pcg_update_seq_ref(alpha, x, r, p, q, dia, rows)
    else:
        interp = backend == "interpret"

        def update(alpha, x, r, p, q):
            return fused_pcg_update(alpha, x, r, p, q, dia, rows=rows,
                                    interpret=interp)

    # seed behaviour: the bundle's standalone precond is the jnp einsum for
    # every backend (used only off the hot path: esrp_init, residual
    # replacement) — keeps cross-backend trajectories bit-identical.
    return SolverOps(backend, mv, mv_dot, lambda r: jacobi_apply(pinv, r),
                     update)


def _swept_ops(backend, layout, precond, mat) -> SolverOps:
    """A non-Jacobi preconditioner: unfused update around its apply."""
    mv, mv_dot = _spmv_pair(backend, layout, mat)

    def update(alpha, x, r, p, q):
        x_new = x + alpha * p
        # barriers: materialize r' before the apply and z' after it, so
        # XLA cannot fuse the axpy / the rᵀz dot into the jnp backend's
        # apply internals (fusions the opaque Pallas calls never get) —
        # keeps the backends bit-identical in f64
        r_new = jax.lax.optimization_barrier(r - alpha * q)
        z_new = jax.lax.optimization_barrier(precond(r_new))
        return x_new, r_new, z_new, r_new @ z_new

    return SolverOps(backend, mv, mv_dot, precond, update)


def _batched_jacobi_ops(cfg: _ProblemOps, mat, dia, pinv) -> SolverOps:
    """Batched Jacobi bundle: hot-loop ops are single batched kernel calls
    (one dispatch for B members); the off-hot-loop precond/dot are
    per-member unrolled so every op keeps the per-member f64 bit-identity
    with the unbatched backend.

    ``fused=True`` routes the jnp hot loop through the fused-batched refs
    instead (one op per iteration for the whole batch — the throughput
    mode; see kernels/spmv/ref.py) and batches precond/dot the same way.
    The Pallas/interpret kernels are already one dispatch per batch either
    way."""
    import jax.numpy as jnp

    from repro.kernels.fused_pcg.fused_pcg import fused_pcg_update_batched
    from repro.kernels.fused_pcg.ref import (fused_pcg_update_ref_fused,
                                             fused_pcg_update_seq_ref_batched)

    backend, batch, rows = cfg.backend, cfg.batch, cfg.rows
    fused = cfg.fused and backend == "jnp"
    matvec, matvec_dot = _spmv_pair(backend, cfg.layout, mat, batch,
                                    fused)
    if fused:
        def update(alpha, x, r, p, q):
            return fused_pcg_update_ref_fused(alpha, x, r, p, q, pinv,
                                              rows=rows)
    elif backend == "jnp":
        def update(alpha, x, r, p, q):
            return fused_pcg_update_seq_ref_batched(alpha, x, r, p, q, dia,
                                                    rows)
    else:
        interp = backend == "interpret"

        def update(alpha, x, r, p, q):
            return fused_pcg_update_batched(alpha, x, r, p, q, dia,
                                            rows=rows, interpret=interp)

    if cfg.fused:
        def precond(r):
            nb, blk, _ = pinv.shape
            return jnp.einsum("nij,bnj->bni", pinv,
                              r.reshape(batch, nb, blk)).reshape(batch, -1)

        def dot(u, v):
            return jnp.einsum("bi,bi->b", u, v)
    else:
        def precond(r):
            return jnp.stack([jacobi_apply(pinv, r[i])
                              for i in range(batch)])

        def dot(u, v):
            return jnp.stack([u[i] @ v[i] for i in range(batch)])

    return SolverOps(backend, matvec, matvec_dot, precond, update, dot=dot)
