"""Silent-data-corruption injection, invariant checks, and localization.

Fail-stop failures announce themselves; SDC does not. Following the
algorithmic-redundancy line of arXiv:1309.0212 (redundant computation makes
corrupted iterates *detectable and repairable*), the driver evaluates cheap
solver invariants on a fixed cadence and, on a violation, routes the run
through the same Alg. 2 reconstruction a fail-stop uses — rolling everyone
back to the clean stored stage and rebuilding the flagged nodes' entries
from the redundancy queue.

The detectors (evaluated every ``check_every`` iterations, one extra SpMV +
one preconditioner apply per check):

  residual       ‖r − (b − A·x)‖ / ‖b‖ — the recurrence residual must track
                 the true residual (van der Vorst/Ye drift, paper Eq. 2).
                 Catches corruption of x or r: a consistent CG update leaves
                 the deviation vector d = r − (b − A x) *invariant*, so an
                 injected e_x (d = −A e_x) or e_r (d = e_r) persists until
                 checked, and its per-node-slab norms localize the corrupted
                 node (± one halo for e_x).
  orthogonality  |rᵀp − rz| / (‖r‖·‖p‖) — entering an iteration, CG's local
                 orthogonality gives rᵀp = rᵀz exactly (p = z + β·p_prev,
                 rᵀp_prev = O(ε)). A corrupted direction p breaks the
                 identity; the violation persists for the following
                 iterations (the Krylov structure is broken), so a check
                 period away it is still visible. NOTE corruption of p does
                 NOT break the residual detector — x and r are updated with
                 the *same* corrupted direction, so r ≡ b − A x is
                 preserved; this second invariant is what catches it.
  z-invariant    ‖z − P·r‖ / ‖z‖ — the carried z must be the preconditioned
                 residual. Catches a bit flip landing in z between its
                 computation and its use in p = z + β·p_prev (the injection
                 model for target="z"; see ``corrupt``). Localizes exactly
                 (P·r is recomputed clean).
  queue-checksum per-push per-node-slab checksums carried in the state
                 (``ESRPState.q_sums`` / ``rq_sums``, written at push time
                 inside the same ``lax.cond``) vs a recompute. Catches
                 corruption of the redundancy copies themselves — which
                 never perturbs the trajectory but would poison a later
                 Alg. 2 read; the same checksums are verified at read time
                 in ``comm.shard.ShardedFailureRuntime.assemble_pair``.

Tolerances are relative, recorded in the reports, and define the detection
floor: a flip below the invariant noise (low-order mantissa bits) is
undetectable but also numerically harmless at that tolerance. All
comparisons are written NaN-safe (``not (v <= tol)``): an exponent-bit flip
that drives the state to inf/NaN *fires* the detectors rather than
vacuously passing them.

Everything here is batch-polymorphic: a batched solve carries (B, M) live
vectors and (3, B, n_slabs) queue checksums, the invariants evaluate
per member, and detection fires when any *live* member violates. Members
whose RHS row is all-zero (the micro-batcher's padding) and members already
converged are excluded — their B=1 reference runs either never existed or
already ended, so nothing about them may fire a repair.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.failures import SDCEvent
from repro.core.pcg import _vec_norm
from repro.sparse.partition import Partition


@dataclasses.dataclass(frozen=True)
class SDCPolicy:
    """Invariant-check cadence and the (recorded) detection tolerances."""

    check_every: int = 16        # invariant-check period (iterations)
    res_rtol: float = 1e-7       # ‖r − (b − Ax)‖ / ‖b‖
    orth_rtol: float = 1e-8      # |rᵀp − rz| / (‖r‖·‖p‖)
    z_rtol: float = 1e-8         # ‖z − P r‖ / ‖z‖
    queue_rtol: float = 1e-9     # per-slab checksum relative mismatch
    flag_frac: float = 0.05      # slab is flagged when its deviation norm
    #                              exceeds this fraction of the max slab
    max_repairs: int = 8         # hard stop against a repair loop that
    #                              cannot clear the violation

    def __post_init__(self):
        if self.check_every < 1:
            raise ValueError(
                f"SDCPolicy.check_every must be >= 1, got {self.check_every}")


@dataclasses.dataclass
class Detection:
    """One fired invariant check (input to the driver's repair routing)."""

    detector: str                # "residual" | "orthogonality" |
    #                              "z-invariant" | "queue-checksum"
    violation: float             # the relative violation that fired
    tol: float                   # the tolerance it was compared against
    flagged: tuple[int, ...]     # localized node set (repair reconstructs
    #                              these; rollback cleans everything else)
    queue_slots: tuple[int, ...] = ()   # queue-checksum: corrupted q slots
    rq_slots: tuple[int, ...] = ()      # queue-checksum: corrupted rq slots


def slab_sums(v: jax.Array, n_slabs: int) -> jax.Array:
    """Per-node-slab checksum of a distributed vector (plain slab sum; the
    push-time and check-time values go through this same helper so a
    mismatch beyond reduction-order noise means the stored copy changed).
    Batch-polymorphic: slabs live on the last axis, so an (M,) vector gives
    (n_slabs,) and a batched (B, M) vector gives per-member (B, n_slabs)."""
    return v.reshape(v.shape[:-1] + (n_slabs, -1)).sum(axis=-1)


# --------------------------------------------------------------------------- #
# injection
# --------------------------------------------------------------------------- #
def _uint_dtype(dtype) -> tuple[object, int]:
    itemsize = jnp.dtype(dtype).itemsize
    return {8: jnp.uint64, 4: jnp.uint32, 2: jnp.uint16}[itemsize], \
        itemsize * 8

def _flip(v: jax.Array, idx: np.ndarray, bit: int) -> jax.Array:
    """XOR bit ``bit`` of the entries at last-axis indices ``idx``.
    Elementwise on the (possibly sharded) array — under the mesh each device
    flips only the entries its own shard holds; on a batched (B, M) vector
    the same columns flip in every member's row (one physical event strikes
    all B members, like fail-stop injection)."""
    ut, nbits = _uint_dtype(v.dtype)
    iv = jax.lax.bitcast_convert_type(v, ut)
    mask = jnp.zeros_like(iv).at[..., jnp.asarray(idx)].set(
        ut(1) << ut(min(bit, nbits - 1)))
    return jax.lax.bitcast_convert_type(iv ^ mask, v.dtype)


def _corrupt_values(v: jax.Array, idx: np.ndarray, ev: SDCEvent) -> jax.Array:
    if ev.kind == "bitflip":
        return _flip(v, idx, ev.bit)
    # perturb scale is per member (max over the member's own row), so a
    # batched member's bump is bit-identical to its B=1 run's
    bump = (ev.scale * jnp.max(jnp.abs(v)) if v.ndim == 1
            else ev.scale * jnp.max(jnp.abs(v), axis=-1, keepdims=True))
    return v.at[..., jnp.asarray(idx)].add(bump)


def _entry_indices(part: Partition, node: int, ev: SDCEvent) -> np.ndarray:
    """Deterministic corrupted-entry choice inside one node's slab."""
    lo, hi = part.node_rows(node)
    rng = np.random.default_rng((ev.seed, ev.iter, node))
    return rng.integers(lo, hi, size=ev.count)


def corrupt(st, ev: SDCEvent, part: Partition):
    """Apply one SDCEvent to an ESRPState (mid-iteration, after the storage
    prelude — the same injection point fail-stop events use).

    target p/r/x: flip entries of the live vector entering the iteration
    (the corrupted values feed the iteration's own update and silently
    propagate). target z: the carried z is consumed into p = z + β·p_prev
    within the same fused update, so a flip landing on z between compute
    and use corrupts *both* — the injection applies the flip to z and adds
    the identical value delta to p (its additive image through the p
    update). target "queue": flip entries of the newest valid redundancy
    copy — the host-visible ``q`` slot slab, and, on the mesh runtime, the
    listed *holder* devices' physical ``rq`` rows; the live trajectory is
    untouched, only a later recovery read would be poisoned.
    """
    idx = np.concatenate([_entry_indices(part, s, ev) for s in ev.nodes])
    pcg = st.pcg
    if ev.target == "p":
        return st._replace(pcg=pcg._replace(p=_corrupt_values(pcg.p, idx, ev)))
    if ev.target == "r":
        return st._replace(pcg=pcg._replace(r=_corrupt_values(pcg.r, idx, ev)))
    if ev.target == "x":
        return st._replace(pcg=pcg._replace(x=_corrupt_values(pcg.x, idx, ev)))
    if ev.target == "z":
        z_bad = _corrupt_values(pcg.z, idx, ev)
        delta = z_bad - pcg.z
        return st._replace(pcg=pcg._replace(z=z_bad, p=pcg.p + delta))
    # target == "queue"
    tags = np.asarray(st.q_tags)
    valid = np.nonzero(tags >= 0)[0]
    slot = int(valid[-1]) if valid.size else 2
    st = st._replace(q=st.q.at[slot].set(_corrupt_values(st.q[slot], idx, ev)))
    if not isinstance(st.rq, tuple):
        # the physical device-resident copies: flip inside the listed holder
        # devices' (width, bn) queue rows — on the batched runtime the same
        # holder rows flip for every member
        w, bn = st.rq.shape[-2], st.rq.shape[-1]
        for d in ev.nodes:
            rng = np.random.default_rng((ev.seed, ev.iter, d, 1))
            flat = rng.integers(0, w * bn, size=ev.count)
            if st.rq.ndim == 5:
                row = st.rq[slot, :, d].reshape(st.rq.shape[1], -1)
                st = st._replace(rq=st.rq.at[slot, :, d].set(
                    _corrupt_values(row, flat, ev).reshape(-1, w, bn)))
            else:
                row = st.rq[slot, d].reshape(-1)
                st = st._replace(rq=st.rq.at[slot, d].set(
                    _corrupt_values(row, flat, ev).reshape(w, bn)))
    return st


# --------------------------------------------------------------------------- #
# invariant evaluation
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnums=(3,))
def _invariant_values(ops, pcg, b, n_slabs):
    """Device computation for one check: the residual-deviation slab norms,
    the orthogonality violation and its slab partials, the z-invariant slab
    norms, and the norms the relative tolerances divide by. Batched (B, M)
    states yield per-member rows ((B, n_slabs) slab profiles, (B,) norms)."""
    d = pcg.r - (b - ops.matvec(pcg.x))
    shp = d.shape[:-1] + (n_slabs, -1)
    dev_slab = jnp.linalg.norm(d.reshape(shp), axis=-1)
    if pcg.r.ndim == 1:
        rp = (pcg.r @ pcg.p if ops.dot is None else ops.dot(pcg.r, pcg.p))
    else:
        rp = (jnp.sum(pcg.r * pcg.p, axis=-1) if ops.dot is None
              else ops.dot(pcg.r, pcg.p))
    orth_slab = (pcg.r * (pcg.p - pcg.z)).reshape(shp).sum(axis=-1)
    dz = pcg.z - ops.precond(pcg.r)
    z_slab = jnp.linalg.norm(dz.reshape(shp), axis=-1)
    return (dev_slab, jnp.abs(rp - pcg.rz), orth_slab, z_slab,
            _vec_norm(pcg.r), _vec_norm(pcg.p), _vec_norm(pcg.z))


def _flag_slabs(slab: np.ndarray, frac: float) -> tuple[int, ...]:
    top = np.nanmax(slab) if np.isfinite(slab).any() else np.inf
    if not np.isfinite(top):
        # inf/NaN deviation: every non-finite slab is suspect
        return tuple(int(s) for s in np.nonzero(~np.isfinite(slab))[0])
    return tuple(int(s) for s in np.nonzero(slab >= frac * top)[0])


def _queue_mismatch(stored, arrays, n_slabs, rtol, reducer):
    """Corrupted (slot, node) pairs among the slots with a valid tag. The
    per-slot comparison is batch-polymorphic: a batched (B, n) checksum row
    flags a node when ANY member's checksum for it mismatches."""
    bad = []
    for slot, tag, stored_row in arrays:
        if tag < 0:
            continue
        actual = np.asarray(reducer(slot))
        ref = np.asarray(stored_row)
        scale = np.abs(ref) + 1.0
        mism = ~(np.abs(actual - ref) <= rtol * scale)    # NaN-safe
        mism = mism.reshape(-1, mism.shape[-1]).any(axis=0)
        for node in np.nonzero(mism)[0]:
            bad.append((slot, int(node)))
    return bad


def _worst_member(vals: np.ndarray, viol: np.ndarray) -> int:
    """Index of the worst violating member (NaN counts as worst-possible)."""
    v = np.where(viol, vals, -np.inf)
    v = np.where(np.isnan(v), np.inf, v)
    return int(np.argmax(v))


def _flag_union(slab2: np.ndarray, viol: np.ndarray,
                frac: float) -> tuple[int, ...]:
    """Union of the violating members' flagged slabs (one member: exactly
    ``_flag_slabs`` of its profile — the unbatched behaviour)."""
    out: set[int] = set()
    for m in np.nonzero(viol)[0]:
        out.update(_flag_slabs(slab2[m], frac))
    return tuple(sorted(out))


def run_checks(ops, st, b, part: Partition, bnorm,
               policy: SDCPolicy, live=None) -> Detection | None:
    """Evaluate every invariant on the current state; return the
    most-localizable fired Detection (queue checksums first — exact
    localization, no rollback needed — then residual, z-invariant,
    orthogonality), or None when all invariants hold.

    Batched states evaluate every relative invariant per member. ``bnorm``
    may be a scalar (unbatched) or a (B,) per-member array; batched runs
    always re-derive the per-member ‖b‖ from ``b`` so zero-RHS padding
    members are excluded from detection even when the caller passed a flat
    norm. ``live`` (optional (B,) bool) further restricts detection to
    members still iterating — a member that already converged ended its
    B=1 reference run before this check existed, so it must not fire one.
    """
    n = part.n_nodes
    q_sums = getattr(st, "q_sums", ())
    rq_sums = getattr(st, "rq_sums", ())

    if not isinstance(q_sums, tuple):
        tags = np.asarray(st.q_tags)
        bad_q = _queue_mismatch(
            q_sums, [(s, int(tags[s]), q_sums[s]) for s in range(3)],
            n, policy.queue_rtol,
            lambda s: slab_sums(st.q[s], n))
        bad_rq = []
        if not isinstance(rq_sums, tuple):
            bad_rq = _queue_mismatch(
                rq_sums, [(s, int(tags[s]), rq_sums[s]) for s in range(3)],
                n, policy.queue_rtol,
                lambda s: st.rq[s].sum(axis=(-2, -1)))
        if bad_q or bad_rq:
            nodes = tuple(sorted({d for _, d in bad_q + bad_rq}))
            return Detection(
                detector="queue-checksum", violation=float("nan"),
                tol=policy.queue_rtol, flagged=nodes,
                queue_slots=tuple(sorted({s for s, _ in bad_q})),
                rq_slots=tuple(sorted({s for s, _ in bad_rq})))

    batched = st.pcg.x.ndim == 2
    (dev_slab, orth, orth_slab, z_slab, rnorm, pnorm,
     znorm) = jax.device_get(_invariant_values(ops, st.pcg, b, n))
    tiny = np.finfo(np.float64).tiny

    # normalize to per-member rows: (B, n_slabs) profiles, (B,) norms —
    # the unbatched state is one member (B = 1, bitwise the legacy values)
    dev2 = np.atleast_2d(np.asarray(dev_slab, np.float64))
    z2 = np.atleast_2d(np.asarray(z_slab, np.float64))
    orth2 = np.atleast_2d(np.asarray(orth_slab, np.float64))
    ov = np.atleast_1d(np.asarray(orth, np.float64))
    rn = np.atleast_1d(np.asarray(rnorm, np.float64))
    pn = np.atleast_1d(np.asarray(pnorm, np.float64))
    zn = np.atleast_1d(np.asarray(znorm, np.float64))
    if batched:
        bn = np.linalg.norm(np.asarray(jax.device_get(b), np.float64),
                            axis=-1)
    else:
        bn = np.atleast_1d(np.asarray(bnorm, np.float64))
    lv = np.ones(dev2.shape[0], bool) if live is None \
        else np.asarray(live, bool).reshape(-1)
    lv = lv & (bn > 0)     # zero-RHS members: frozen padding, never flagged
    # NaN/inf in a corrupted member's profile is a *signal* here (the
    # NaN-safe comparisons below turn it into a fired detector), not an
    # arithmetic error worth a warning
    with np.errstate(invalid="ignore", over="ignore", divide="ignore"):
        return _state_checks(dev2, z2, orth2, ov, rn, pn, zn, bn, lv,
                             policy, tiny)


def _state_checks(dev2, z2, orth2, ov, rn, pn, zn, bn, lv,
                  policy: SDCPolicy, tiny: float) -> Detection | None:
    res_rel = np.linalg.norm(dev2, axis=-1) / np.maximum(bn, tiny)
    viol = ~(res_rel <= policy.res_rtol) & lv              # NaN-safe
    if viol.any():
        k = _worst_member(res_rel, viol)
        return Detection(detector="residual", violation=float(res_rel[k]),
                         tol=policy.res_rtol,
                         flagged=_flag_union(dev2, viol, policy.flag_frac))

    z_rel = np.linalg.norm(z2, axis=-1) / np.maximum(zn, tiny)
    viol = ~(z_rel <= policy.z_rtol) & lv
    if viol.any():
        k = _worst_member(z_rel, viol)
        return Detection(detector="z-invariant", violation=float(z_rel[k]),
                         tol=policy.z_rtol,
                         flagged=_flag_union(z2, viol, policy.flag_frac))

    denom = rn * pn
    orth_rel = ov / np.maximum(denom, tiny)
    # ‖r‖·‖p‖ overflowed (r passed the residual check, so this is ‖p‖):
    # a clean finite direction cannot — the ratio that would hide the
    # violation (huge/inf → 0) is an overflow artifact, not a pass
    orth_rel = np.where(np.isfinite(denom), orth_rel, np.inf)
    viol = ~(orth_rel <= policy.orth_rtol) & lv
    if viol.any():
        # a corrupted direction contaminates every slab through the global
        # α/β scalars — no sound per-slab localization exists. Flag each
        # violating member's largest |rᵀ(p − z)| partial (the corrupted
        # entries dominate it for the flips above the detection floor);
        # repair correctness never depends on the guess, because the
        # rollback discards ALL live vectors and rebuilds from clean
        # storage.
        k = _worst_member(orth_rel, viol)
        flags: set[int] = set()
        for m in np.nonzero(viol)[0]:
            a = np.abs(orth2[m])
            a = np.where(np.isfinite(a), a, np.inf)
            flags.add(int(np.argmax(a)))
        return Detection(detector="orthogonality",
                         violation=float(orth_rel[k]),
                         tol=policy.orth_rtol,
                         flagged=tuple(sorted(flags)))
    return None


def device_violation(ops, st, b, thresh, policy: SDCPolicy,
                     rnorm=None) -> jax.Array:
    """On-device boolean: does any live member violate a state invariant or
    a queue checksum at the current iterate? This is the chunk-tail guard
    (``esrp.run_chunk(sdc_check=...)``): a fire halts the chunk at the exact
    check boundary — before the iteration's storage prelude can commit
    corrupted state — and the host then runs the authoritative
    ``run_checks`` localization on the halted state. Thresholds and member
    exclusions mirror ``run_checks``; the two may disagree only within a
    ulp of the tolerance, which is orders below any injected corruption.
    """
    pcg = st.pcg
    tiny = jnp.asarray(jnp.finfo(b.dtype).tiny, b.dtype)
    bn = _vec_norm(b)
    rn = _vec_norm(pcg.r) if rnorm is None else rnorm
    # NaN-safe liveness: a member whose ‖r‖ went NaN is the OPPOSITE of
    # converged — ~(rn < thresh) keeps it live where (rn >= thresh) would
    # silently mask it from every detector
    live = ~(rn < thresh) & (bn > 0)
    d = pcg.r - (b - ops.matvec(pcg.x))
    res = _vec_norm(d) / jnp.maximum(bn, tiny)
    dz = pcg.z - ops.precond(pcg.r)
    zrel = _vec_norm(dz) / jnp.maximum(_vec_norm(pcg.z), tiny)
    rp = (pcg.r @ pcg.p if pcg.r.ndim == 1
          else jnp.sum(pcg.r * pcg.p, axis=-1))
    denom = rn * _vec_norm(pcg.p)
    orth = jnp.abs(rp - pcg.rz) / jnp.maximum(denom, tiny)
    orth = jnp.where(jnp.isfinite(denom), orth, jnp.inf)
    bad = (~(res <= policy.res_rtol) | ~(zrel <= policy.z_rtol)
           | ~(orth <= policy.orth_rtol))                  # NaN-safe
    fired = jnp.any(bad & live)
    if not isinstance(st.q_sums, tuple):
        nsl = st.q_sums.shape[-1]
        sums = jnp.stack([slab_sums(st.q[s], nsl) for s in range(3)])
        mism = ~(jnp.abs(sums - st.q_sums)
                 <= policy.queue_rtol * (jnp.abs(st.q_sums) + 1.0))
        valid = (st.q_tags >= 0).reshape((3,) + (1,) * (mism.ndim - 1))
        fired = fired | jnp.any(mism & valid)
    if not isinstance(st.rq_sums, tuple):
        rsums = st.rq.sum(axis=(-2, -1))
        mism = ~(jnp.abs(rsums - st.rq_sums)
                 <= policy.queue_rtol * (jnp.abs(st.rq_sums) + 1.0))
        valid = (st.q_tags >= 0).reshape((3,) + (1,) * (mism.ndim - 1))
        fired = fired | jnp.any(mism & valid)
    return fired
