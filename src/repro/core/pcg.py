"""Preconditioned conjugate gradient (paper Alg. 1, [Saad'03 Alg. 9.1]).

Operator-based and fully jittable. The hot path runs through a ``SolverOps``
bundle (repro.core.ops): the SpMV and the pᵀq dot fuse into one pass, and
lines 4-7 of Alg. 1 fuse into a single vector pass (kernels/fused_pcg), with
a pure-jnp reference backend that is bit-identical in f64. The closure-based
entry points (``pcg_step``, ``run_pcg``) wrap arbitrary (matvec, precond)
pairs — they power the dense test operators and the *inner* reconstruction
solves of Alg. 2 (lines 6/8), which the paper runs to rtol 1e-14.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.marks import sync_free
from repro.core.ops import SolverOps, batch_ops, make_closure_ops


def _expand(s: jax.Array, v: jax.Array) -> jax.Array:
    """Broadcast a per-member scalar against per-member vectors: with
    unbatched (M,) vectors the scalar passes through untouched (the
    pre-batch expression, bit-for-bit); with batched (B, M) vectors the
    (B,) scalar gains a trailing axis."""
    return s[..., None] if v.ndim == 2 else s


def _vec_norm(r: jax.Array) -> jax.Array:
    """||r|| per member: flat norm for (M,), per-row norm for (B, M).
    The row-wise reduce is bit-identical in f64 to the flat norm of each
    row (asserted in tests/test_batched.py)."""
    return jnp.linalg.norm(r) if r.ndim == 1 else jnp.linalg.norm(r, axis=-1)


def freeze_pcg(old: "PCGState", new: "PCGState", done: jax.Array) -> "PCGState":
    """Per-member freeze: members with done=True keep their old per-member
    leaves; the shared iteration counter always advances (it tracks the
    global schedule, not any one member)."""
    col = done[:, None]
    return PCGState(x=jnp.where(col, old.x, new.x),
                    r=jnp.where(col, old.r, new.r),
                    z=jnp.where(col, old.z, new.z),
                    p=jnp.where(col, old.p, new.p),
                    rz=jnp.where(done, old.rz, new.rz),
                    beta=jnp.where(done, old.beta, new.beta),
                    j=new.j)


class PCGState(NamedTuple):
    """Dynamic solver state (paper §1.1: vectors + scalars changing per iter).

    Entering iteration j the fields hold: x = x^(j), r = r^(j), z = z^(j),
    p = p^(j), rz = r^(j)ᵀz^(j), beta = β^(j-1), j = j.
    """
    x: jax.Array
    r: jax.Array
    z: jax.Array
    p: jax.Array
    rz: jax.Array
    beta: jax.Array
    j: jax.Array


def pcg_init(matvec: Callable, precond: Callable, b: jax.Array,
             x0: jax.Array | None = None,
             dot: Callable | None = None) -> PCGState:
    """``dot`` overrides the r₀ᵀz₀ reduction (SolverOps.dot): the sharded
    runtime's per-node partial sums and its single-device mesh mirror must
    agree bitwise from iteration 0, which a flat ``@`` would break."""
    x0 = jnp.zeros_like(b) if x0 is None else x0
    r0 = b - matvec(x0)
    z0 = precond(r0)
    rz0 = r0 @ z0 if dot is None else dot(r0, z0)
    # beta shape follows the batch layout: () for (M,) b, (B,) for (B, M)
    return PCGState(x=x0, r=r0, z=z0, p=z0, rz=rz0,
                    beta=jnp.zeros(b.shape[:-1], b.dtype),
                    j=jnp.zeros((), jnp.int32))


def pcg_iterate_ops(state: PCGState, ops: SolverOps) -> PCGState:
    """One PCG iteration through the SolverOps bundle (Alg. 1 lines 3-8).

    The SpMV produces pᵀq in the same pass (α without re-reading p, q) and
    the x/r/z/rz updates run as one fused sweep. ESRP's storage bookkeeping
    happens *before* this call (Alg. 3 swaps SpMV ↔ ASpMV without touching
    the numerics), so the failure-free trajectory is bit-identical to plain
    PCG — the paper's trajectory-identity property.
    """
    q, pq = ops.matvec_dot(state.p)
    alpha = state.rz / pq
    x, r, z, rz = ops.update(alpha, state.x, state.r, state.p, q)
    beta = rz / state.rz
    p = z + _expand(beta, z) * state.p
    return PCGState(x=x, r=r, z=z, p=p, rz=rz, beta=beta, j=state.j + 1)


def pcg_iterate(state: PCGState, q: jax.Array,
                precond: Callable) -> PCGState:
    """One PCG iteration *given* q = A·p^(j) — the unfused reference form
    (kept for callers that computed q themselves)."""
    alpha = state.rz / (state.p @ q)
    x = state.x + alpha * state.p
    r = state.r - alpha * q
    z = precond(r)
    rz = r @ z
    beta = rz / state.rz
    p = z + beta * state.p
    return PCGState(x=x, r=r, z=z, p=p, rz=rz, beta=beta, j=state.j + 1)


def pcg_step(state: PCGState, matvec: Callable,
             precond: Callable) -> PCGState:
    return pcg_iterate_ops(state, make_closure_ops(matvec, precond))


# Per-iteration telemetry columns of the on-device metrics ring (obs=on):
# the iteration's rz, its storage push/star flags, and the orthogonality
# invariant residual |r^T p - rz| — the same signal core.sdc's host-side
# orthogonality check thresholds, here recorded every iteration.
METRIC_FIELDS = ("rz", "push", "star", "orth")


def iteration_metrics(pcg, push, star) -> jax.Array:
    """One (len(METRIC_FIELDS),) on-device metrics row for the iteration
    that just produced ``pcg``. Stacked into a single small vector so the
    chunk scan carries one extra row per iteration next to the ||r|| record
    and the whole ring reads back with the existing chunk readback (zero
    extra dispatches)."""
    dt = pcg.rz.dtype
    if pcg.r.ndim == 1:
        orth = jnp.abs(pcg.r @ pcg.p - pcg.rz)
        return jnp.stack([pcg.rz, jnp.asarray(push).astype(dt),
                          jnp.asarray(star).astype(dt), orth])
    # batched: one (len(METRIC_FIELDS), B) row — per-member rz/orth columns,
    # the shared push/star flags broadcast across members
    ones = jnp.ones(pcg.rz.shape, dt)
    orth = jnp.abs(jnp.sum(pcg.r * pcg.p, axis=-1) - pcg.rz)
    return jnp.stack([pcg.rz, ones * jnp.asarray(push).astype(dt),
                      ones * jnp.asarray(star).astype(dt), orth])


@sync_free
def scan_with_convergence_freeze(st, step: Callable, rnorm0: jax.Array,
                                 n_iters: int,
                                 thresh: jax.Array | None,
                                 aux0: jax.Array | None = None,
                                 freeze: Callable | None = None):
    """Scan ``n_iters`` of ``step`` (state -> (state, ||r||)), recording
    ||r|| after each iteration — the chunked-convergence protocol shared by
    the ESRP and IMCR chunk runners.

    With ``thresh`` set (dynamic), the carried ||r|| doubles as a done flag:
    once it drops below thresh the remaining iterations pass the state
    through untouched (``lax.cond``), so the caller's returned state *is*
    the state at first convergence and no chunk ever needs re-running.
    thresh=None runs all n_iters unconditionally.

    ``aux0`` arms the metrics ring (obs=on): ``step`` then returns
    (state, ||r||, aux) and the record becomes ``(norms, auxes)`` — frozen
    iterations repeat the carried aux row, which the driver trims away with
    the executed count. aux0=None keeps the exact pre-telemetry trace (the
    jaxpr-identity tests compare against this path).

    Batched (rnorm0 of shape (B,), thresh (B,)): the freeze becomes
    **per-member** (continuous batching). Each iteration steps the whole
    batch, then ``freeze(old_state, new_state, done)`` re-selects the old
    per-member leaves for converged members (``done`` = (B,) bool) — the
    caller supplies it because only the strategy knows which state leaves
    carry the batch axis where. A converged member's state is therefore
    exactly its state at first convergence, bit-for-bit, while stragglers
    advance; a global ``lax.cond`` still skips the whole body once every
    member is done. The recorded norms become (n_iters, B).
    """
    batched = thresh is not None and getattr(rnorm0, "ndim", 0) > 0
    if batched and freeze is None:
        raise ValueError("batched convergence freeze needs the per-member "
                         "freeze(old, new, done) callback")
    if batched:
        if aux0 is not None:
            def advance_aux(carry):
                s, rnorm, aux = carry
                s2, rn2, aux2 = step(s)
                done = rnorm < thresh
                return (freeze(s, s2, done), jnp.where(done, rnorm, rn2),
                        jnp.where(done[None, :], aux, aux2))

            def body_aux(carry, _):
                carry = jax.lax.cond(jnp.all(carry[1] < thresh),
                                     lambda c: c, advance_aux, carry)
                return carry, (carry[1], carry[2])

            (st, _, _), record = jax.lax.scan(
                body_aux, (st, rnorm0, aux0), None, length=n_iters)
            return st, record

        def advance(carry):
            s, rnorm = carry
            s2, rn2 = step(s)
            done = rnorm < thresh
            return freeze(s, s2, done), jnp.where(done, rnorm, rn2)

        def body(carry, _):
            carry = jax.lax.cond(jnp.all(carry[1] < thresh),
                                 lambda c: c, advance, carry)
            return carry, carry[1]

        (st, _), norms = jax.lax.scan(body, (st, rnorm0), None,
                                      length=n_iters)
        return st, norms

    if aux0 is not None:
        def body_aux(carry, _):
            s, rnorm, aux = carry
            if thresh is None:
                s, rnorm, aux = step(s)
            else:
                s, rnorm, aux = jax.lax.cond(
                    rnorm < thresh, lambda s: (s, rnorm, aux), step, s)
            return (s, rnorm, aux), (rnorm, aux)

        (st, _, _), record = jax.lax.scan(body_aux, (st, rnorm0, aux0), None,
                                          length=n_iters)
        return st, record

    def body(carry, _):
        s, rnorm = carry
        if thresh is None:
            s, rnorm = step(s)
        else:
            s, rnorm = jax.lax.cond(
                rnorm < thresh, lambda s: (s, rnorm), step, s)
        return (s, rnorm), rnorm

    (st, _), norms = jax.lax.scan(body, (st, rnorm0), None, length=n_iters)
    return st, norms


@sync_free
def scan_with_halt_guard(st, step: Callable, rnorm0: jax.Array,
                         n_iters: int,
                         thresh: jax.Array | None,
                         aux0: jax.Array | None = None,
                         freeze: Callable | None = None,
                         guard: Callable | None = None):
    """``scan_with_convergence_freeze`` plus an on-device *halt guard*: before
    each iteration executes, ``guard(state, ||r||)`` is evaluated on the
    entering state; once it fires the remaining iterations of the chunk pass
    the state through untouched. This is how the SDC invariants ride inside
    the chunk (ROADMAP: detection latency bounded by ``check_every`` even
    when chunks are long): the guard fires at a check boundary, the chunk
    freezes *at* that boundary — before the boundary iteration's storage
    prelude can commit corrupted state — and the host runs the authoritative
    localization on the returned state.

    The record gains a per-iteration halted flag: ``halted[i] = True`` means
    iteration i did NOT execute (the state returned is the state entering
    it). Convergence/freeze semantics are identical to
    ``scan_with_convergence_freeze`` — a fired guard simply acts like
    all-members-converged from that iteration on.
    """
    batched = thresh is not None and getattr(rnorm0, "ndim", 0) > 0
    if batched and freeze is None:
        raise ValueError("batched convergence freeze needs the per-member "
                         "freeze(old, new, done) callback")
    if guard is None:
        raise ValueError("scan_with_halt_guard needs a guard callback")
    h0 = jnp.zeros((), bool)

    def all_done(rnorm):
        if thresh is None:
            return jnp.zeros((), bool)
        return jnp.all(rnorm < thresh) if batched else rnorm < thresh

    def body(carry, _):
        s, rnorm, aux, halted = carry
        # once halted (j pinned at the boundary) or fully converged the guard
        # is skipped — the remaining iterations are pure passthrough
        halted = halted | jax.lax.cond(
            halted | all_done(rnorm), lambda: jnp.zeros((), bool),
            lambda: guard(s, rnorm))

        def advance(c):
            s, rnorm, aux, halted = c
            if aux is None:
                s2, rn2 = step(s)
                aux2 = None
            else:
                s2, rn2, aux2 = step(s)
            if batched:
                done = rnorm < thresh
                s2 = freeze(s, s2, done)
                rn2 = jnp.where(done, rnorm, rn2)
                if aux is not None:
                    aux2 = jnp.where(done[None, :], aux, aux2)
            return (s2, rn2, aux2, halted)

        carry = jax.lax.cond(halted | all_done(rnorm), lambda c: c,
                             advance, (s, rnorm, aux, halted))
        rec = ((carry[1], carry[3]) if aux0 is None
               else (carry[1], carry[2], carry[3]))
        return carry, rec

    (st, _, _, _), record = jax.lax.scan(
        body, (st, rnorm0, aux0, h0), None, length=n_iters)
    return st, record


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4))
def run_pcg(matvec: Callable, precond: Callable, b: jax.Array,
            rtol: float = 1e-8, max_iters: int = 100_000,
            x0: jax.Array | None = None) -> tuple[PCGState, jax.Array]:
    """Solve to ||r||/||b|| < rtol. Returns (state, relative residual).

    ||r|| is carried in the loop state: computed once per iteration (in the
    body, after the step) instead of once in ``cond`` and again in ``body``.

    b = 0 returns x = 0 with relative residual 0.0 exactly: without the
    guard, thresh = rtol·||b|| = 0 never beats ||r|| = 0 (the ≥ keeps
    looping), α = rz/pᵀq = 0/0 poisons the state with NaN, and rel =
    0/0 = NaN — which the Alg. 2 line-6/8 inner solves would then scatter
    into a reconstructed state (a zero RHS there is a legal input: e.g. a
    failed block whose residual strip is exactly zero).
    """
    return _solve(make_closure_ops(matvec, precond), b, rtol, max_iters, x0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def solve_pcg(ops: SolverOps, b: jax.Array, rtol: float = 1e-8,
              max_iters: int = 100_000, x0: jax.Array | None = None
              ) -> tuple[PCGState, jax.Array]:
    """``run_pcg`` through a bundle (its ``dot`` is not used): the
    program takes the bundle's operands as arguments."""
    return _solve(ops, b, rtol, max_iters, x0)


def _solve(ops, b, rtol, max_iters, x0):
    state = pcg_init(ops.matvec, ops.precond, b, x0)
    bnorm = jnp.linalg.norm(b)
    thresh = rtol * bnorm
    nonzero = bnorm > 0

    def cond(carry):
        s, rnorm = carry
        return (rnorm >= thresh) & (s.j < max_iters) & nonzero

    def body(carry):
        s, _ = carry
        s = pcg_iterate_ops(s, ops)
        return s, jnp.linalg.norm(s.r)

    state, rnorm = jax.lax.while_loop(
        cond, body, (state, jnp.linalg.norm(state.r)))
    # b = 0 ⇒ the exact solution is x = 0 whatever x0 was; rebuild the
    # consistent state rather than handing back the untouched initial guess
    state = jax.tree.map(
        lambda a: jnp.where(nonzero, a, jnp.zeros_like(a)), state)
    return state, jnp.where(nonzero, rnorm / jnp.where(nonzero, bnorm, 1.0),
                            jnp.zeros_like(rnorm))


@functools.partial(jax.jit, static_argnums=(0, 1, 3, 4))
def run_pcg_batched(matvec: Callable, precond: Callable, b: jax.Array,
                    rtol: float = 1e-8, max_iters: int = 100_000,
                    x0: jax.Array | None = None
                    ) -> tuple[PCGState, jax.Array]:
    """Batched ``run_pcg``: solve B systems with the *same* operator to
    per-member tolerance. b: (B, M); matvec/precond are the unbatched
    closures, applied per member through ``batch_ops``.

    ``jax.vmap`` of the while_loop would keep stepping converged members
    until the last straggler finishes (vmap has no per-member freeze), which
    breaks the per-member trajectory identity. Here the loop runs while any
    member is active and ``freeze_pcg`` pins converged members at their
    first-convergence state — member i's final (state, rel) is bit-identical
    in f64 to ``run_pcg(matvec, precond, b[i], ...)``. Zero-RHS members
    (the micro-batcher's padding) resolve to x = 0 / rel = 0 at iteration 0,
    exactly like the unbatched guard."""
    return _solve_batched(make_closure_ops(matvec, precond), b, rtol,
                          max_iters, x0)


@functools.partial(jax.jit, static_argnums=(2, 3))
def solve_pcg_batched(ops: SolverOps, b: jax.Array, rtol: float = 1e-8,
                      max_iters: int = 100_000,
                      x0: jax.Array | None = None
                      ) -> tuple[PCGState, jax.Array]:
    """``run_pcg_batched`` through an unbatched bundle, applied per
    member: the program takes the bundle's operands as arguments."""
    return _solve_batched(ops, b, rtol, max_iters, x0)


def _solve_batched(ops, b, rtol, max_iters, x0):
    nb = b.shape[0]
    ops = batch_ops(ops, nb)
    state = pcg_init(ops.matvec, ops.precond, b, x0, dot=ops.dot)
    bnorm = jnp.linalg.norm(b, axis=-1)
    thresh = rtol * bnorm
    nonzero = bnorm > 0

    def cond(carry):
        s, rnorm = carry
        return jnp.any((rnorm >= thresh) & nonzero) & (s.j < max_iters)

    def body(carry):
        s, rnorm = carry
        s2 = pcg_iterate_ops(s, ops)
        rn2 = jnp.linalg.norm(s2.r, axis=-1)
        done = (rnorm < thresh) | ~nonzero
        return freeze_pcg(s, s2, done), jnp.where(done, rnorm, rn2)

    state, rnorm = jax.lax.while_loop(
        cond, body, (state, jnp.linalg.norm(state.r, axis=-1)))
    live = nonzero if state.x.ndim == 1 else nonzero[:, None]
    state = PCGState(
        x=jnp.where(live, state.x, 0.0), r=jnp.where(live, state.r, 0.0),
        z=jnp.where(live, state.z, 0.0), p=jnp.where(live, state.p, 0.0),
        rz=jnp.where(nonzero, state.rz, 0.0),
        beta=jnp.where(nonzero, state.beta, 0.0), j=state.j)
    return state, jnp.where(nonzero, rnorm / jnp.where(nonzero, bnorm, 1.0),
                            jnp.zeros_like(rnorm))


def residual_drift(matvec: Callable, b: jax.Array, x_end: jax.Array,
                   r_end: jax.Array) -> jax.Array:
    """Paper Eq. (2): (||r_end|| - ||b - A x_end||) / ||b - A x_end||.
    Batch-polymorphic: (B, M) inputs give a (B,) per-member drift."""
    true_res = _vec_norm(b - matvec(x_end))
    return (_vec_norm(r_end) - true_res) / true_res
