"""ESR with periodic storage (ESRP) — paper §3, Alg. 3.

The storage stage runs the augmented SpMV in two consecutive iterations every
T iterations (j ≡ 0 and j ≡ 1 mod T, j > 2) and pushes the current search
direction into a queue of THREE redundant copies, so that a failure landing
after the first push of a stage still finds two *consecutive* directions from
the previous stage (Fig. 1). At the second push each node also duplicates its
local x, r, z, p and the replicated β — the rollback anchor for survivors.

Implementation notes (vs. the paper listing):
  * The SpMV and ASpMV produce the *same numbers*; ASpMV only adds redundancy
    traffic. We therefore always compute q = A·p once and gate only the
    bookkeeping on the schedule — the failure-free trajectory is bit-identical
    to plain PCG (the paper's trajectory-identity property, tested).
  * Storage bookkeeping is ``jax.lax.cond``-gated: the (3, M) redundancy
    queue rotation and the starred-locals duplication execute *only* on
    storage iterations. (The seed's ``jnp.where`` over the whole state tree
    copied the queue every iteration — pure overhead on the T-2 non-storage
    iterations of each period. ``gated=False`` keeps that path for the
    before/after microbenchmark in benchmarks/run.py.)
  * β capture: the paper stages β through β** (line 6) and commits at line 10.
    Entering the *second* storage iteration j₀+1, the live β variable already
    holds β^(j₀) — exactly the value Alg. 2 needs to reconstruct iteration
    j₀+1 — so we capture β* := β directly at the second push. This is
    equivalent to the β**/β* two-phase dance (the paper needs it only because
    its listing captures *before* the iteration-j₀ β update) and is covered by
    the mid-stage failure tests.
  * With T = 1 both schedule conditions hold every iteration; only the push
    branch runs and recovery reads the *live* state — that is exactly ESR
    (paper §3: "For T = 1 ... corresponds to regular ESR").
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.analysis.marks import sync_free
from repro.core.ops import SolverOps
from repro.core.pcg import (METRIC_FIELDS, PCGState, _vec_norm, freeze_pcg,
                            iteration_metrics, pcg_init, pcg_iterate_ops,
                            scan_with_convergence_freeze,
                            scan_with_halt_guard)


class ESRPState(NamedTuple):
    pcg: PCGState
    q: jax.Array          # (3, M) redundant copies of p (newest = slot 2).
    #                       Block-row placement: each node's rows are its OWN
    #                       pushed history (survivor anchor); the copies a
    #                       failure recovers from live in ``rq``.
    q_tags: jax.Array     # (3,) int32 iteration of each copy, -1 = empty
    x_s: jax.Array        # starred locals (rollback anchor), iteration j*
    r_s: jax.Array
    z_s: jax.Array
    p_s: jax.Array
    beta_s: jax.Array     # β* = β^(j*-1)
    rz_s: jax.Array       # r*ᵀz* (avoids a recompute on rollback)
    star_tag: jax.Array   # j*, -1 = none
    rq: jax.Array | tuple = ()   # device-resident redundancy-queue copies:
    #                       (3, n_nodes, width, bn), node axis sharded over
    #                       the mesh — row d holds the tile values node d
    #                       received at each storage push (paper §2.2.1's
    #                       queue entry on the designated neighbours). Empty
    #                       tuple on the single-device simulator. Tags are
    #                       shared with ``q_tags``.
    q_sums: jax.Array | tuple = ()   # (3, n_slabs) per-node-slab checksums
    #                       of each q copy, written at push time under the
    #                       same lax.cond — the SDC detector and the
    #                       recovery read recompute and compare (a mismatch
    #                       means the stored copy was corrupted after the
    #                       push). Empty tuple = checksums disabled.
    rq_sums: jax.Array | tuple = ()  # (3, n_nodes) per-holder-device
    #                       checksums of each rq entry (same protocol).


def esrp_init(matvec, precond, b: jax.Array,
              x0: jax.Array | None = None,
              dot=None, n_slabs: int = 0) -> ESRPState:
    """n_slabs > 0 enables the per-push queue checksums (one slab sum per
    node); 0 keeps them off (legacy callers, microbenchmarks)."""
    pcg = pcg_init(matvec, precond, b, x0, dot)
    z = jnp.zeros_like(b)
    return ESRPState(
        pcg=pcg,
        q=jnp.zeros((3,) + b.shape, b.dtype),
        q_tags=jnp.full((3,), -1, jnp.int32),
        x_s=z, r_s=z, z_s=z, p_s=z,
        beta_s=jnp.zeros(b.shape[:-1], b.dtype),
        rz_s=jnp.zeros(b.shape[:-1], b.dtype),
        star_tag=jnp.full((), -1, jnp.int32),
        # checksum rows follow the batch layout of b: (3, n_slabs) for (M,)
        # rhs, (3, B, n_slabs) for (B, M) — one slab-sum row per member
        q_sums=(jnp.zeros((3,) + b.shape[:-1] + (n_slabs,), b.dtype)
                if n_slabs > 0 else ()))


def storage_flags(j: jax.Array, T: int):
    """(push?, star?) for iteration j — Alg. 3 lines 4/7 schedule."""
    if T == 1:                      # ESR: push every iteration, no stars
        return j > 2, jnp.zeros((), bool)
    push1 = (j % T == 0) & (j > 2)
    push2 = ((j - 1) % T == 0) & (j > 2)
    return push1 | push2, push2


def push_queue(st: ESRPState, tag: jax.Array, push=None) -> ESRPState:
    """ASpMV side effect: rotate the queue-of-3, newest copy = current p.

    ``push`` (comm.shard.redundancy_queue) is the *physical* redundancy
    send: it ppermutes/retains the current p's column tiles onto their
    designated holder devices and the received payload rotates into ``rq``
    — the device-resident queue entry recovery reads on the mesh."""
    q = jnp.concatenate([st.q[1:], st.pcg.p[None]], axis=0)
    tags = jnp.concatenate([st.q_tags[1:], tag[None]])
    st = st._replace(q=q, q_tags=tags)
    if not isinstance(st.q_sums, tuple):
        n_slabs = st.q_sums.shape[-1]
        p = st.pcg.p
        s = p.reshape(p.shape[:-1] + (n_slabs, -1)).sum(axis=-1)
        st = st._replace(
            q_sums=jnp.concatenate([st.q_sums[1:], s[None]], axis=0))
    if push is not None:
        entry = push(st.pcg.p)        # (n_nodes, width, bn), (B, ...) batched
        st = st._replace(rq=jnp.concatenate([st.rq[1:], entry[None]], axis=0))
        if not isinstance(st.rq_sums, tuple):
            es = entry.sum(axis=(-2, -1))
            st = st._replace(
                rq_sums=jnp.concatenate([st.rq_sums[1:], es[None]], axis=0))
    return st


def capture_stars(st: ESRPState, tag: jax.Array) -> ESRPState:
    """Second storage iteration: duplicate locals (Alg. 3 lines 9-10).

    Entering iteration j the live fields are x^(j), r^(j), z^(j), p^(j) and
    beta = β^(j-1) — precisely the reconstruction point's requirements.
    """
    p = st.pcg
    return st._replace(x_s=p.x, r_s=p.r, z_s=p.z, p_s=p.p,
                       beta_s=p.beta, rz_s=p.rz, star_tag=tag)


def member_select(old: ESRPState, new: ESRPState,
                  done: jax.Array) -> ESRPState:
    """Per-member freeze for the batched state: members with done=True keep
    every per-member leaf (pcg vectors/scalars, their queue rows, starred
    locals, checksums) from ``old``; shared bookkeeping — the iteration
    counter, queue tags, star tag — always advances with the global
    schedule. This is the ``freeze`` callback the batched chunk scan and
    the driver's converged-member restore both use."""
    col = done[:, None]
    st = new._replace(
        pcg=freeze_pcg(old.pcg, new.pcg, done),
        q=jnp.where(done[None, :, None], old.q, new.q),
        x_s=jnp.where(col, old.x_s, new.x_s),
        r_s=jnp.where(col, old.r_s, new.r_s),
        z_s=jnp.where(col, old.z_s, new.z_s),
        p_s=jnp.where(col, old.p_s, new.p_s),
        beta_s=jnp.where(done, old.beta_s, new.beta_s),
        rz_s=jnp.where(done, old.rz_s, new.rz_s))
    if not isinstance(new.rq, tuple):
        mask = done.reshape((1, -1) + (1,) * (new.rq.ndim - 2))
        st = st._replace(rq=jnp.where(mask, old.rq, new.rq))
    if not isinstance(new.q_sums, tuple):
        st = st._replace(
            q_sums=jnp.where(done[None, :, None], old.q_sums, new.q_sums))
    if not isinstance(new.rq_sums, tuple):
        st = st._replace(
            rq_sums=jnp.where(done[None, :, None], old.rq_sums, new.rq_sums))
    return st


def esrp_prelude(st: ESRPState, T: int, gated: bool = True,
                 push=None) -> ESRPState:
    """The storage bookkeeping of iteration j (everything that happens at the
    (A)SpMV point, *before* the numeric update). Split out so the failure
    driver can inject a failure exactly mid-iteration, after the push.

    gated=True executes the push/star branches under ``lax.cond`` — on the
    non-storage iterations of the period nothing is copied *and no
    redundancy traffic moves* (``push``'s ppermutes run only on storage
    iterations, like the paper's ASpMV swap-in). gated=False is the seed's
    ``jnp.where``-over-the-state-tree (copies the queue every iteration;
    kept for the microbenchmark comparison).
    """
    j = st.pcg.j
    do_push, star = storage_flags(j, T)
    if gated:
        st = jax.lax.cond(do_push, lambda s: push_queue(s, j, push),
                          lambda s: s, st)
        st = jax.lax.cond(star, lambda s: capture_stars(s, j), lambda s: s,
                          st)
    else:
        st = jax.tree.map(
            lambda a, b: jnp.where(do_push, a, b), push_queue(st, j, push),
            st)
        st = jax.tree.map(
            lambda a, b: jnp.where(star, a, b), capture_stars(st, j), st)
    return st


def numeric_step(pcg: PCGState, ops: SolverOps,
                 b: jax.Array | None = None, rr_every: int = 0,
                 gated: bool = True) -> PCGState:
    """The PCG update plus the residual-replacement gate — everything of an
    ESRP iteration *except* the storage prelude.

    rr_every > 0 enables *residual replacement* [van der Vorst & Ye '00 —
    the drift mechanism the paper's Eq. 2 measures]: every rr_every
    iterations the recursive residual is replaced by the true b - A x (and
    z, rz, p's conjugation base refresh accordingly), keeping the Eq. 2
    drift near zero at the cost of one extra SpMV per period. Extension
    beyond the paper (its §"Accuracy of the experiments" discusses but does
    not implement replacement). With gated=True the replacement SpMV +
    precond run under ``lax.cond`` — no extra SpMV executes on the other
    rr_every - 1 iterations of each period.

    This is also the driver's post-recovery resume step: re-running the
    reconstruction-point iteration must skip its storage prelude (the push
    already happened pre-failure) but NOT the replacement gate — a bare
    ``pcg_iterate_ops`` would silently drop a replacement landing on the
    resume iteration and fork the post-recovery trajectory off the
    failure-free one.
    """
    pcg = pcg_iterate_ops(pcg, ops)
    if rr_every > 0 and b is not None:
        do = (pcg.j % rr_every == 0) & (pcg.j > 0)

        def replace(s: PCGState) -> PCGState:
            r_true = b - ops.matvec(s.x)
            z_true = ops.precond(r_true)
            rz = (r_true @ z_true if ops.dot is None
                  else ops.dot(r_true, z_true))
            return s._replace(r=r_true, z=z_true, rz=rz)

        if gated:
            pcg = jax.lax.cond(do, replace, lambda s: s, pcg)
        else:
            pcg = jax.tree.map(lambda a_, b_: jnp.where(do, a_, b_),
                               replace(pcg), pcg)
    return pcg


def esrp_step(st: ESRPState, ops: SolverOps, T: int,
              b: jax.Array | None = None, rr_every: int = 0,
              gated: bool = True, push=None) -> ESRPState:
    """One full ESRP iteration: bookkeeping + the PCG update (Alg. 3 body).
    See ``numeric_step`` for the residual-replacement semantics."""
    st = esrp_prelude(st, T, gated, push)
    return st._replace(pcg=numeric_step(st.pcg, ops, b, rr_every, gated))


@functools.partial(jax.jit, static_argnums=(2, 3, 5, 6, 8, 9, 10))
@sync_free
def run_chunk(st: ESRPState, ops: SolverOps, T: int, n_iters: int,
              thresh: jax.Array | None = None,
              rr_every: int = 0, gated: bool = True,
              b: jax.Array | None = None, push=None,
              metrics: bool = False, sdc_check=None):
    """Run n_iters ESRP iterations, recording ||r|| after each (the paper
    checks convergence every iteration; the driver scans the record).

    ``thresh`` (dynamic) arms the sync-free convergence protocol (see
    ``pcg.scan_with_convergence_freeze``): the driver never has to re-run a
    chunk to land exactly on the convergence iteration — the returned state
    *is* the state at first convergence — and can overlap the norm-record
    readback of one chunk with the dispatch of the next. thresh=None runs
    all n_iters unconditionally.

    ``metrics`` (static, obs=on) extends the scan record with the on-device
    metrics ring: the return becomes (state, (norms, aux)) with one
    ``pcg.METRIC_FIELDS`` row per iteration (the executed iteration's
    storage flags + the post-iteration rz / orthogonality residual), read
    back together with the norm record. metrics=False compiles to exactly
    the pre-telemetry jaxpr (tested).

    ``sdc_check`` (static, a hashable ``sdc.SDCPolicy``) arms the on-device
    invariant guard: at every check boundary (the cadence, plus every
    storage iteration — the check-before-store protocol) the entering state
    is verified by ``sdc.device_violation`` inside the scan; a violation
    halts the chunk *at* that boundary, before the boundary iteration's
    storage prelude could commit corrupted state. The record gains a
    per-iteration halted flag ((norms, halted) / (norms, aux, halted)) and
    detection latency is bounded by the check cadence regardless of chunk
    length. sdc_check=None keeps the exact guard-free scan (the
    jaxpr-identity tests compare against this path).
    """

    def step(s):
        s2 = esrp_step(s, ops, T, b=b, rr_every=rr_every, gated=gated,
                       push=push)
        rnorm = _vec_norm(s2.pcg.r)
        if not metrics:
            return s2, rnorm
        do_push, star = storage_flags(s.pcg.j, T)
        return s2, rnorm, iteration_metrics(s2.pcg, do_push, star)

    aux0 = (jnp.zeros((len(METRIC_FIELDS),) + st.pcg.rz.shape,
                      st.pcg.rz.dtype) if metrics else None)
    batched = st.pcg.x.ndim == 2
    freeze = member_select if batched else None
    if sdc_check is None:
        return scan_with_convergence_freeze(
            st, step, _vec_norm(st.pcg.r), n_iters, thresh, aux0,
            freeze=freeze)

    from repro.core import sdc as sdc_mod

    def guard(s, rnorm):
        j = s.pcg.j
        at = (j > 0) & (j % sdc_check.check_every == 0)
        if T < (1 << 29):
            # ESRP storage iterations are check boundaries too (the driver's
            # check-before-store protocol); the "none" runner's T = 1 << 30
            # sentinel stores nothing, so only the cadence applies there
            at = at | ((j > 2) & ((j % T == 0) | ((j - 1) % T == 0)))
        th = -jnp.inf if thresh is None else thresh
        return jax.lax.cond(
            at,
            lambda: sdc_mod.device_violation(ops, s, b, th, sdc_check,
                                             rnorm=rnorm),
            lambda: jnp.zeros((), bool))

    return scan_with_halt_guard(
        st, step, _vec_norm(st.pcg.r), n_iters, thresh, aux0,
        freeze=freeze, guard=guard)


def recovery_point(st: ESRPState, T: int):
    """Which iteration can be reconstructed from the queue?

    Returns (target_iter, prev_slot, curr_slot); target -1 if unrecoverable
    (failure before the first completed storage stage — driver restarts).
    Newest consecutive pair wins: (1,2) if tags[2] == tags[1]+1 else (0,1)
    if tags[1] == tags[0]+1 (the Fig. 1 queue states, incl. the mid-stage
    case where the newest copy has no consecutive partner yet).
    """
    t = [int(x) for x in st.q_tags]
    if t[2] >= 0 and t[2] == t[1] + 1:
        return t[2], 1, 2
    if t[1] >= 0 and t[1] == t[0] + 1:
        return t[1], 0, 1
    return -1, -1, -1
