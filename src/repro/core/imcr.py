"""In-memory buddy checkpoint-restart (IMCR) — paper §3.1.

Every T iterations each node sends a complete copy of its local parts of all
dynamic vectors (x, r, z, p) plus the replicated scalars to its φ buddy
neighbours (same neighbour function as ASpMV, Eq. 1). Recovery: replacements
fetch their parts from a buddy; survivors roll back to their own local copy.
Unlike ESR/ESRP this introduces a brand-new round of communication per
checkpoint (4 full local vectors × φ buddies) instead of piggybacking on the
SpMV — the communication-volume asymmetry the paper highlights.

The checkpoint copy (4 full vectors + scalars) is ``lax.cond``-gated on the
schedule, like ESRP's queue push: on the T-1 non-checkpoint iterations of
each period nothing is copied, and the numeric update runs through the same
``SolverOps`` bundle as ESRP/plain PCG.
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
                            scan_with_convergence_freeze)


class IMCRState(NamedTuple):
    pcg: PCGState
    ck_x: jax.Array
    ck_r: jax.Array
    ck_z: jax.Array
    ck_p: jax.Array
    ck_beta: jax.Array
    ck_rz: jax.Array
    ck_tag: jax.Array      # iteration of the checkpoint, -1 = none
    # simulated buddy traffic: checksum of the rolled (sent) buffers keeps the
    # data movement alive in the compiled graph so failure-free timing on this
    # single-device simulator includes the checkpoint sends.
    traffic: jax.Array


def imcr_init(matvec, precond, b: jax.Array,
              x0: jax.Array | None = None, dot=None) -> IMCRState:
    pcg = pcg_init(matvec, precond, b, x0, dot)
    z = jnp.zeros_like(b)
    zero = jnp.zeros(b.shape[:-1], b.dtype)   # () unbatched, (B,) batched
    return IMCRState(pcg=pcg, ck_x=z, ck_r=z, ck_z=z, ck_p=z,
                     ck_beta=zero, ck_rz=zero,
                     ck_tag=jnp.full((), -1, jnp.int32),
                     traffic=jnp.zeros((), b.dtype))


def checkpoint(st: IMCRState, phi: int, rows_per_node: int) -> IMCRState:
    """Push local state copies to φ buddies (simulated as ring rolls)."""
    p = st.pcg
    traffic = st.traffic
    stacked = jnp.stack([p.x, p.r, p.z, p.p])
    for k in range(1, phi + 1):
        shift = ((k + 1) // 2) * rows_per_node * (1 if k % 2 else -1)
        # roll along the row axis (last): batched stacks are (4, B, M)
        traffic = traffic + jnp.sum(
            jnp.roll(stacked, shift, axis=stacked.ndim - 1)) * 0.0
    return st._replace(ck_x=p.x, ck_r=p.r, ck_z=p.z, ck_p=p.p,
                       ck_beta=p.beta, ck_rz=p.rz, ck_tag=p.j,
                       traffic=traffic)


def member_select(old: IMCRState, new: IMCRState,
                  done: jax.Array) -> IMCRState:
    """Per-member freeze for the batched state (see esrp.member_select):
    converged members keep their pcg leaves and checkpoint copies; the
    shared iteration counter / checkpoint tag / simulated traffic follow
    the global schedule."""
    col = done[:, None]
    return new._replace(
        pcg=freeze_pcg(old.pcg, new.pcg, done),
        ck_x=jnp.where(col, old.ck_x, new.ck_x),
        ck_r=jnp.where(col, old.ck_r, new.ck_r),
        ck_z=jnp.where(col, old.ck_z, new.ck_z),
        ck_p=jnp.where(col, old.ck_p, new.ck_p),
        ck_beta=jnp.where(done, old.ck_beta, new.ck_beta),
        ck_rz=jnp.where(done, old.ck_rz, new.ck_rz))


def imcr_step(st: IMCRState, ops: SolverOps, T: int, phi: int,
              rows_per_node: int, gated: bool = True) -> IMCRState:
    j = st.pcg.j
    do_ck = (j % T == 0) & (j > 2)
    if gated:
        st = jax.lax.cond(do_ck,
                          lambda s: checkpoint(s, phi, rows_per_node),
                          lambda s: s, st)
    else:
        st = jax.tree.map(lambda a, b: jnp.where(do_ck, a, b),
                          checkpoint(st, phi, rows_per_node), st)
    return st._replace(pcg=pcg_iterate_ops(st.pcg, ops))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 7, 8))
@sync_free
def run_chunk(st: IMCRState, ops: SolverOps, T: int, phi: int,
              rows_per_node: int, n_iters: int,
              thresh: jax.Array | None = None, gated: bool = True,
              metrics: bool = False):
    """Run n_iters IMCR iterations, recording ||r|| after each. Same
    convergence-freeze protocol as esrp.run_chunk (shared via
    ``pcg.scan_with_convergence_freeze``): once the carried ||r|| drops
    below ``thresh`` the remaining iterations pass the state through, so
    the driver never re-runs the final chunk.

    ``metrics`` (static) arms the same on-device metrics ring as
    esrp.run_chunk — here the "push" column records the buddy-checkpoint
    schedule (j % T == 0, j > 2) and "star" is always 0 (IMCR has no
    starred-locals anchor)."""

    def step(s):
        s2 = imcr_step(s, ops, T, phi, rows_per_node, gated)
        rnorm = _vec_norm(s2.pcg.r)
        if not metrics:
            return s2, rnorm
        do_ck = (s.pcg.j % T == 0) & (s.pcg.j > 2)
        return s2, rnorm, iteration_metrics(s2.pcg, do_ck,
                                            jnp.zeros((), bool))

    aux0 = (jnp.zeros((len(METRIC_FIELDS),) + st.pcg.rz.shape,
                      st.pcg.rz.dtype) if metrics else None)
    batched = st.pcg.x.ndim == 2
    return scan_with_convergence_freeze(
        st, step, _vec_norm(st.pcg.r), n_iters, thresh, aux0,
        freeze=member_select if batched else None)


def check_survivable(failed: list[int], phi: int, n_nodes: int) -> None:
    """Per-event recoverability check (buddy-copy survival analysis).

    Each node ships its checkpoint to its φ ring buddies (Eq. 1 neighbour
    function), so a failed node is recoverable iff at least one of its φ
    buddies survives. For |failed| ≤ φ that is automatic: killing node s
    *and* all φ of its buddies takes φ+1 failures. |failed| > φ may still
    be survivable for a lucky (spread-out) failed set — mirrored on the
    ESRP side by ``RedundancyPlan.survives`` — so the check walks the
    actual buddy sets instead of hard-failing on the count.
    """
    from repro.sparse.partition import neighbors

    failed_set = set(failed)
    for s in failed:
        if not set(neighbors(s, phi, n_nodes)) - failed_set:
            raise RuntimeError(
                f"node {s} and all phi={phi} of its checkpoint buddies "
                f"failed together ({sorted(failed_set)}) — no surviving "
                f"copy to fetch from")


def recover(st: IMCRState) -> PCGState:
    """Roll everyone back to the checkpoint (replacements fetch from buddies,
    survivors restore their own copy — in the simulator both are the stored
    full vectors, valid because buddies of the ≤ φ failed nodes survive)."""
    return PCGState(x=st.ck_x, r=st.ck_r, z=st.ck_z, p=st.ck_p,
                    rz=st.ck_rz, beta=st.ck_beta, j=st.ck_tag)
