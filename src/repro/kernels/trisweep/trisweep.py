"""Blocked triangular-sweep Pallas kernel — the substrate of the SSOR and
IC(0) preconditioner applies.

Solves (D̂ + T) y = r by block substitution, where T is a strictly
block-triangular matrix stored ELL-style at the preconditioner block
granularity b and D̂ is block-diagonal with *precomputed inverse* blocks
(``dinv``): every diagonal solve is a dense (b x b) @ (b,) matvec.

  forward  (reverse=False):  y_i = dinv_i (r_i - sum_{k} T[i,k] y_{idx[i,k]})
                             rows processed 0 .. nbr-1 (all idx[i,k] < i)
  backward (reverse=True):   same recurrence, rows nbr-1 .. 0 (idx[i,k] > i)

Grid: (nbr,), one block row per step — TPU grids execute *sequentially*, so
step t may read the y blocks written by earlier steps: the output BlockSpec
is the full (M,) vector with a constant index map, which pins y in VMEM for
the whole sweep (no HBM round-trip between rows). The per-row index/count
arrays ride in as scalar prefetch (SMEM), exactly like the Block-ELL SpMV's
column indices. Padding slots point at block 0 with zero data; loads of
not-yet-written y regions are masked before the multiply (the output buffer
is uninitialized, and NaN * 0 = NaN would otherwise leak in).

The whole input vector plus the T strip must fit VMEM (M up to ~200k f64 on
a 16 MB core) — the regime of the paper's per-node subdomains. The k-slot
accumulation is sequential (fori_loop), so the jnp reference
(``ref.block_sweep_ref``) reproduces the reduction order bit-for-bit in f64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import not_on_chip


def _sweep_kernel(idx_ref, n_ref, data_ref, dinv_ref, r_ref, y_ref,
                  *, reverse: bool, nbr: int):
    t = pl.program_id(0)
    i = (nbr - 1 - t) if reverse else t          # row this step owns
    b = r_ref.shape[0]
    kmax = data_ref.shape[1]
    acc = r_ref[...]

    def slot(k, acc):
        j = idx_ref[i, k]
        yj = y_ref[pl.ds(j * b, b)]
        yj = jnp.where(k < n_ref[i], yj, jnp.zeros_like(yj))
        return acc - jnp.dot(data_ref[0, k], yj,
                             preferred_element_type=acc.dtype)

    acc = jax.lax.fori_loop(0, kmax, slot, acc)
    y_ref[pl.ds(i * b, b)] = jnp.dot(dinv_ref[0], acc,
                                     preferred_element_type=acc.dtype)


def _wavefront_kernel(rows_ref, n_ref, idx_ref, data_ref, dinv_ref, r_ref,
                      y_ref):
    t = pl.program_id(0)
    width = rows_ref.shape[1]
    kmax = idx_ref.shape[2]
    b = dinv_ref.shape[-1]

    def row(w, _):
        i = rows_ref[t, w]                       # padding rows point at the
        acc = r_ref[pl.ds(i * b, b)]             # scratch block i = nbr
        def slot(k, acc):
            j = idx_ref[t, w, k]
            yj = y_ref[pl.ds(j * b, b)]
            yj = jnp.where(k < n_ref[t, w], yj, jnp.zeros_like(yj))
            return acc - jnp.dot(data_ref[0, w, k], yj,
                                 preferred_element_type=acc.dtype)
        acc = jax.lax.fori_loop(0, kmax, slot, acc)
        y_ref[pl.ds(i * b, b)] = jnp.dot(dinv_ref[0, w], acc,
                                         preferred_element_type=acc.dtype)
        return 0

    jax.lax.fori_loop(0, width, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wavefront_sweep(rows: jax.Array, n: jax.Array, idx: jax.Array,
                    data: jax.Array, dinv: jax.Array, r: jax.Array,
                    *, interpret: bool = False) -> jax.Array:
    """Level-scheduled (wavefront) blocked triangular sweep.

    Inputs are the level-major arrays of a ``precond.blocktri.LevelSchedule``
    (one grid step per elimination-DAG level, all of the level's independent
    block rows processed in that step): rows (n_levels, width) int32 row ids
    with padding = nbr; n/idx/data/dinv per (level, slot). The work vector is
    (m + b): the trailing scratch block absorbs padding-row writes (their
    ``dinv`` is zero), so the kernel has no per-row branch. Per-row
    arithmetic — masked slot loads, sequential k accumulation, one dense
    diagonal matvec — is exactly the sequential kernel's, so the result is
    bit-identical to ``block_sweep`` in f64 (rows within a level are
    mutually independent by construction).
    """
    if not interpret:
        not_on_chip("wavefront triangular sweep",
                    "rank-1 blocks that are not (8, 128) tiles")
    n_levels, width, kmax, b, _ = data.shape
    m = r.shape[0]
    mp = m + b
    r_pad = jnp.concatenate([r, jnp.zeros((b,), r.dtype)])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_levels,),
        in_specs=[
            pl.BlockSpec((1, width, kmax, b, b),
                         lambda t, *_: (t, 0, 0, 0, 0)),
            pl.BlockSpec((1, width, b, b), lambda t, *_: (t, 0, 0, 0)),
            pl.BlockSpec((mp,), lambda t, *_: (0,)),
        ],
        out_specs=pl.BlockSpec((mp,), lambda t, *_: (0,)),
    )
    y = pl.pallas_call(
        _wavefront_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((mp,), r.dtype),
        interpret=interpret,
    )(rows, n, idx, data, dinv, r_pad)
    return y[:m]


@functools.partial(jax.jit, static_argnames=("reverse", "interpret"))
def block_sweep(idx: jax.Array, n: jax.Array, data: jax.Array,
                dinv: jax.Array, r: jax.Array, *, reverse: bool = False,
                interpret: bool = False) -> jax.Array:
    """idx: (nbr, kmax) int32 column-block ids (0-padded); n: (nbr,) int32
    valid slots; data: (nbr, kmax, b, b); dinv: (nbr, b, b); r: (m,).
    Returns y with (D̂ + T) y = r."""
    if not interpret:
        not_on_chip("triangular sweep",
                    "rank-1 blocks that are not (8, 128) tiles")
    nbr, kmax, b, _ = data.shape
    m = r.shape[0]
    if m != nbr * b:
        raise ValueError(f"M={m} != nbr*b = {nbr}*{b}")
    row = (lambda t, idx, n: (nbr - 1 - t,)) if reverse else \
        (lambda t, idx, n: (t,))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nbr,),
        in_specs=[
            pl.BlockSpec((1, kmax, b, b), lambda t, idx, n: row(t, idx, n) + (0, 0, 0)),
            pl.BlockSpec((1, b, b), lambda t, idx, n: row(t, idx, n) + (0, 0)),
            pl.BlockSpec((b,), row),
        ],
        out_specs=pl.BlockSpec((m,), lambda t, idx, n: (0,)),
    )
    return pl.pallas_call(
        functools.partial(_sweep_kernel, reverse=reverse, nbr=nbr),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m,), r.dtype),
        interpret=interpret,
    )(idx, n, data, dinv, r)
