"""Pure-jnp oracles for the Block-ELL SpMV kernels.

``spmv_ref`` is the free-form einsum oracle used by the kernel validation
sweeps. ``spmv_seq_ref`` / ``spmv_dot_ref`` mirror the Pallas kernels'
*reduction structure* (sequential accumulation over the k slots, per-row-tile
dot partials): on the same inputs they produce bit-identical f64 results to
the kernels, which is what lets the trajectory-identity property be asserted
exactly across the jnp and Pallas ``SolverOps`` backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def spmv_ref(data: jax.Array, idx: jax.Array, x: jax.Array) -> jax.Array:
    """data: (rt, kmax, bm, bn); idx: (rt, kmax); x: (ct*bn,) -> (rt*bm,)."""
    rt, kmax, bm, bn = data.shape
    xb = x.reshape(-1, bn)
    gathered = xb[idx]                                    # (rt, kmax, bn)
    out = jnp.einsum("rkij,rkj->ri", data, gathered)
    return out.reshape(rt * bm)


_NT_BATCHED = (((1,), (2,)), ((0,), (0,)))   # per row tile r: x_r · tile_rᵀ


def tile_products(tiles: jax.Array, xg: jax.Array) -> jax.Array:
    """(rt, bm, bn) tiles, (rt, bn) gathered x tiles -> (rt, bm): every row
    tile's tile · x as the kernel forms it, the NT matmul x · tileᵀ."""
    return jax.lax.dot_general(xg, tiles, _NT_BATCHED,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=tiles.dtype)


@jax.jit
def spmv_seq_ref(data: jax.Array, idx: jax.Array, x: jax.Array) -> jax.Array:
    """SpMV with the kernel's accumulation order: acc += data[:, k] @ x_k,
    k ascending — one ``tile_products`` per slot, summed sequentially
    exactly like the Pallas grid's inner dimension. Jitted, so that it is
    one fused program whether it is called eagerly or from a solver's."""
    rt, kmax, bm, bn = data.shape
    xb = x.reshape(-1, bn)
    acc = jnp.zeros((rt, bm), data.dtype)
    for k in range(kmax):
        acc = acc + tile_products(data[:, k], xb[idx[:, k]])
    return acc.reshape(rt * bm)


@jax.jit
def spmv_dot_ref(data: jax.Array, idx: jax.Array,
                 x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Fused y = A @ x and xᵀy, mirroring ``spmv_dot``'s per-row-tile
    partial-sum order. Returns (y, xᵀy)."""
    rt, kmax, bm, bn = data.shape
    xb = x.reshape(-1, bn)
    acc = jnp.zeros((rt, bm), data.dtype)
    for k in range(kmax):
        acc = acc + tile_products(data[:, k], xb[idx[:, k]])
    partial = jnp.sum(acc * x.reshape(rt, bm), axis=1)    # (rt,)
    # keep the (per-row-tile partials -> final sum) association: without the
    # barrier XLA collapses the two reduces into one flat sum, breaking the
    # bit-identity with the kernel's (rt,) partial output.
    partial = jax.lax.optimization_barrier(partial)
    return acc.reshape(rt * bm), jnp.sum(partial)


# --------------------------------------------------------------------------- #
# batched (leading B axis): per-member unrolled loops over the scalar refs.
# A fused batched einsum ("rij,brj->bri") is NOT bit-identical per member to
# the scalar einsum in f64 — XLA picks a different contraction order — so the
# batched refs apply the exact scalar subgraph to each member row and stack.
# That makes batched-vs-B×(B=1) trajectory identity hold by construction.
# --------------------------------------------------------------------------- #
def spmv_seq_ref_batched(data: jax.Array, idx: jax.Array,
                         x: jax.Array) -> jax.Array:
    """x: (B, ct*bn) -> (B, rt*bm); member i identical to spmv_seq_ref(x[i])."""
    return jnp.stack([spmv_seq_ref(data, idx, x[i])
                      for i in range(x.shape[0])])


def spmv_dot_ref_batched(data: jax.Array, idx: jax.Array,
                         x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched fused y = A @ x and xᵀy. Returns ((B, rt*bm), (B,))."""
    pairs = [spmv_dot_ref(data, idx, x[i]) for i in range(x.shape[0])]
    return (jnp.stack([y for y, _ in pairs]),
            jnp.stack([d for _, d in pairs]))


# --------------------------------------------------------------------------- #
# fused-batched variants: ONE batched einsum per k slot serves all B members.
# On an op-overhead-bound host backend this is what actually amortizes the
# batch (the unrolled refs above emit B subgraphs per iteration — B x the op
# count); the price is that member i's rounding is no longer bit-identical
# to its B=1 run (XLA contracts "rij,brj->bri" in a different order). The
# k-slot accumulation order and the per-row-tile partial association are
# kept, so the deviation is einsum-internal only (~ulp level). Opt-in via
# SolverOps fused batching (solve_resilient(batch_fused=True)).
# --------------------------------------------------------------------------- #
def spmv_seq_ref_fused(data: jax.Array, idx: jax.Array,
                       x: jax.Array) -> jax.Array:
    """x: (B, ct*bn) -> (B, rt*bm); one einsum per k slot for all members."""
    rt, kmax, bm, bn = data.shape
    nb = x.shape[0]
    xb = x.reshape(nb, -1, bn)
    acc = jnp.zeros((nb, rt, bm), data.dtype)
    for k in range(kmax):
        acc = acc + jnp.einsum("rij,brj->bri", data[:, k], xb[:, idx[:, k]])
    return acc.reshape(nb, rt * bm)


def spmv_dot_ref_fused(data: jax.Array, idx: jax.Array,
                       x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Batched fused y = A @ x and xᵀy, one einsum per slot for the whole
    batch. Returns ((B, rt*bm), (B,))."""
    rt, kmax, bm, bn = data.shape
    nb = x.shape[0]
    xb = x.reshape(nb, -1, bn)
    acc = jnp.zeros((nb, rt, bm), data.dtype)
    for k in range(kmax):
        acc = acc + jnp.einsum("rij,brj->bri", data[:, k], xb[:, idx[:, k]])
    partial = jnp.sum(acc * x.reshape(nb, rt, bm), axis=2)       # (B, rt)
    partial = jax.lax.optimization_barrier(partial)
    return acc.reshape(nb, rt * bm), jnp.sum(partial, axis=1)
