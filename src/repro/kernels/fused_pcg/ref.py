"""Pure-jnp references for the fused PCG update.

``fused_pcg_update_ref`` is the plain oracle (einsum block apply, flat dot)
that the kernel validation sweeps compare against with tolerances.
``fused_pcg_update_seq_ref`` mirrors the kernel step for step — the
diagonal-wise block apply in the same expression shape, and the same
``rz_dot`` association — so the jnp SolverOps backend is bit-identical
(f64) to the kernel-backed one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_pcg.fused_pcg import (_geometry, _update_tile,
                                               rz_dot)


def fused_pcg_update_ref(alpha, x, r, p, q, pinv_blocks):
    x_new = x + alpha * p
    r_new = r - alpha * q
    nb, b, _ = pinv_blocks.shape
    z_new = jnp.einsum("nij,nj->ni", pinv_blocks,
                       r_new.reshape(nb, b)).reshape(-1)
    return x_new, r_new, z_new, r_new @ z_new


def fused_pcg_update_seq_ref(alpha, x, r, p, q, dia, rows: int):
    """The kernel's arithmetic on the whole (M/L, L) view at once (it is
    elementwise, so position-independent), then the same ``rz_dot``."""
    m = x.shape[0]
    block, lanes, _, _ = _geometry(m, dia, rows)
    x_new, r_new, z_new = (v.reshape(m) for v in _update_tile(
        alpha, *(v.reshape(-1, lanes) for v in (x, r, p, q)), dia, block,
        lambda v, s: jnp.roll(v, s, axis=1)))
    return x_new, r_new, z_new, rz_dot(r_new, z_new, rows)


def fused_pcg_update_seq_ref_batched(alpha, x, r, p, q, dia, rows: int):
    """Batched mirror: per-member unrolled loop over the unbatched one.

    alpha: (B,); x, r, p, q: (B, M). Applying the exact unbatched subgraph
    to each member row (rather than a fused batched op) is what keeps each
    member bit-identical in f64 to its own B=1 run. Returns per-member
    (x', r', z') stacked (B, M) and rz' (B,)."""
    outs = [fused_pcg_update_seq_ref(alpha[i], x[i], r[i], p[i], q[i], dia,
                                     rows)
            for i in range(x.shape[0])]
    return tuple(jnp.stack([o[j] for o in outs]) for j in range(4))


def fused_pcg_update_ref_fused(alpha, x, r, p, q, pinv_blocks, rows: int):
    """Fused-batched update: one einsum/axpy serves all B members.

    The throughput-mode counterpart of the unrolled batched mirror above
    (see the fused-batched note in kernels/spmv/ref.py): per-member
    results match the B=1 run to ~ulp, not bit-exactly. alpha: (B,);
    x, r, p, q: (B, M); returns (B, M) triples and rz' (B,)."""
    a = alpha[:, None]
    x_new = x + a * p
    r_new = r - a * q
    nbatch = x.shape[0]
    nb, b, _ = pinv_blocks.shape
    z_new = jnp.einsum("nij,bnj->bni", pinv_blocks,
                       r_new.reshape(nbatch, nb, b)).reshape(nbatch, -1)
    partial = jnp.sum((r_new * z_new).reshape(nbatch, -1, rows), axis=2)
    partial = jax.lax.optimization_barrier(partial)
    return x_new, r_new, z_new, jnp.sum(partial, axis=1)
