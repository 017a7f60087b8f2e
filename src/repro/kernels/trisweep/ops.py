"""Dispatch wrapper for the blocked triangular sweep (sequential and
level-scheduled/wavefront forms)."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import resolve_backend
from repro.kernels.trisweep.ref import block_sweep_ref, wavefront_sweep_ref
from repro.kernels.trisweep.trisweep import block_sweep, wavefront_sweep


class Wavefront(NamedTuple):
    """Device-side level-major sweep arrays (see blocktri.LevelSchedule).
    A pytree, so it threads through jit; hashable layout comes from the
    caller keeping one instance per preconditioner."""
    rows: jax.Array      # (n_levels, width) int32, padding = nbr
    n: jax.Array         # (n_levels, width) int32
    idx: jax.Array       # (n_levels, width, kmax) int32
    data: jax.Array      # (n_levels, width, kmax, b, b)
    dinv: jax.Array      # (n_levels, width, b, b)


def wavefront_from_schedule(sched) -> Wavefront:
    """Upload a host-side ``blocktri.LevelSchedule`` to device arrays."""
    return Wavefront(rows=jnp.asarray(sched.rows), n=jnp.asarray(sched.n),
                     idx=jnp.asarray(sched.idx),
                     data=jnp.asarray(sched.data),
                     dinv=jnp.asarray(sched.dinv))


def sweep(idx, n, data, dinv, r, *, reverse: bool = False,
          backend: str = "auto", schedule: Wavefront | None = None):
    """Solve (D̂ + T) y = r. With ``schedule`` set, the level-scheduled
    wavefront kernels run one grid step per elimination-DAG level (all
    independent block rows of a level together) instead of one per row —
    bit-identical results either way (same per-row arithmetic)."""
    backend = resolve_backend(backend, r.dtype)
    if schedule is not None:
        if backend == "jnp":
            return wavefront_sweep_ref(schedule.rows, schedule.n,
                                       schedule.idx, schedule.data,
                                       schedule.dinv, r)
        return wavefront_sweep(schedule.rows, schedule.n, schedule.idx,
                               schedule.data, schedule.dinv, r,
                               interpret=(backend == "interpret"))
    if backend == "jnp":
        return block_sweep_ref(idx, n, data, dinv, r, reverse=reverse)
    return block_sweep(idx, n, data, dinv, r, reverse=reverse,
                       interpret=(backend == "interpret"))
