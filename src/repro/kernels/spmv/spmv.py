"""Block-ELL SpMV Pallas TPU kernel — the paper's per-iteration hot spot.

TPU adaptation of the paper's CSR SpMV (GSL on CPU): the matrix is stored as
dense (bm x bn) tiles with per-slot column-tile indices. The key TPU
mechanism is ``PrefetchScalarGridSpec``: the int32 column-index array is
prefetched to SMEM *before* the kernel runs, so the x-tile gather is a
BlockSpec ``index_map`` lookup — the DMA engine streams exactly the needed
x tiles HBM->VMEM while the MXU does the (bm x bn) · (bn,) products. Padding
slots point at column-tile 0 with zero data, so no in-kernel branching.

Grid: (B, row_tiles, kmax) — one kernel serves a batch of B right-hand
sides, and a single vector is the B = 1 case. The accumulator lives in a
VMEM scratch; slot k==0 zeroes it, slot k==kmax-1 writes out — one HBM
write per row tile.

Layout (Mosaic's (8, 128) rule: the last two dims of every block are
multiples of (8, 128) or equal the array's own): vectors travel as
(B, tiles, 1, width) views, so an x tile is a (1, bn) lane row and an
output tile a (1, bm) lane row, whatever bm and bn are. The column indices
are prefetched flat: SMEM pads a 2-D array's last dim to 128 words. The
product is the NT matmul x_tile · dataᵀ at full f32 precision. bm = bn =
128 is the chip geometry (one lane-dense tile per step); the tests sweep
small tiles in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import i32

_NT = (((1,), (1,)), ((), ()))      # contract the lane dims: x · dataᵀ


def _tile_product(x_row, tile):
    """(1, bn) x row, (bm, bn) tile -> (bm,) tile · x: the NT matmul at
    full f32 precision (``ref.tile_products`` is its batched mirror)."""
    return jax.lax.dot_general(x_row, tile, _NT,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=tile.dtype)[0]


def _accumulate(data_ref, x_ref, acc_ref):
    """One grid step (b, r, k): acc += tile(r, k) · x tile(idx[r, k]).
    Returns whether this was the row tile's last slot."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_product(x_ref[0, 0], data_ref[0, 0])
    return k == pl.num_programs(2) - 1


def _spmv_kernel(idx_ref, data_ref, x_ref, o_ref, acc_ref):
    @pl.when(_accumulate(data_ref, x_ref, acc_ref))
    def _flush():
        o_ref[0, 0] = acc_ref[...][None, :]


def _spmv_dot_kernel(idx_ref, data_ref, x_ref, xrow_ref, o_ref, prod_ref,
                     acc_ref):
    """SpMV plus the elementwise x⊙(Ax) of each row tile: at the flush slot
    the freshly accumulated y row tile is still in VMEM, so the dot's
    products cost one (bm,) read of x and no second pass over y. The
    products leave as lane-dense rows; the caller reduces them in a pinned
    order (per row tile, then across tiles)."""
    @pl.when(_accumulate(data_ref, x_ref, acc_ref))
    def _flush():
        o_ref[0, 0] = acc_ref[...][None, :]
        prod_ref[0, 0] = acc_ref[...][None, :] * xrow_ref[0, 0]


def _reduce_tiles(prod):
    """(rt, bm) products -> dot: per-row-tile sums, then the sum over
    tiles (the same association as ``ref.spmv_dot_ref``)."""
    partial = jax.lax.optimization_barrier(jnp.sum(prod, axis=1))
    return jnp.sum(partial)


def _call(kernel, data, idx, x, n_out: int, with_rows: bool, interpret):
    """Grid (B, rt, kmax) with k innermost (sequential): each (b, r) cell
    accumulates through the same VMEM-scratch slot sequence, so member b
    of a batch gets exactly what a lone call on x[b] gets. The matrix
    tiles and the prefetched (flattened, SMEM-sized) index array are
    shared across members."""
    rt, kmax, bm, bn = data.shape
    nb = x.shape[0]
    row = pl.BlockSpec((1, 1, 1, bm), lambda b, r, k, idx: i32(b, r, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, bm, bn), lambda b, r, k, idx: i32(r, k, 0, 0)),
        pl.BlockSpec((1, 1, 1, bn),
                     lambda b, r, k, idx: i32(b, idx[r * kmax + k], 0, 0)),
    ]
    args = [x.reshape(nb, -1, 1, bn)]
    if with_rows:
        in_specs.append(row)
        args.append(x.reshape(nb, rt, 1, bm))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb, rt, kmax),
        in_specs=in_specs,
        out_specs=(row,) * n_out,
        scratch_shapes=[pltpu.VMEM((bm,), data.dtype)],
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((nb, rt, 1, bm), data.dtype),) * n_out,
        interpret=interpret,
    )(idx.reshape(-1), data, *args)
    return [o.reshape(nb, rt, bm) for o in outs]


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_batched(data: jax.Array, idx: jax.Array, x: jax.Array,
                 *, interpret: bool = False) -> jax.Array:
    """data: (rt, kmax, bm, bn); idx: (rt, kmax) int32; x: (B, ct*bn).
    Returns y with y[i] = A @ x[i], shape (B, rt*bm): one pass for all B
    members."""
    (y,) = _call(_spmv_kernel, data, idx, x, 1, False, interpret)
    return y.reshape(x.shape[0], -1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spmv_dot_batched(data: jax.Array, idx: jax.Array, x: jax.Array,
                     *, interpret: bool = False
                     ) -> tuple[jax.Array, jax.Array]:
    """Fused y = A @ x and xᵀy for B members in one kernel pass.

    The PCG step needs q = A·p and then α = rz / pᵀq; unfused that is a full
    second read of p and q from HBM. Here the products for each row tile are
    formed while the y tile is still in VMEM (the x row tile rides along as
    one extra (bm,) input). Needs rt*bm == ct*bn (square A). Returns
    (y: (B, rt*bm), xᵀy: (B,)), each member reduced on its own."""
    y, prod = _call(_spmv_dot_kernel, data, idx, x, 2, True, interpret)
    return (y.reshape(x.shape[0], -1),
            jnp.stack([_reduce_tiles(pm) for pm in prod]))


def spmv(data: jax.Array, idx: jax.Array, x: jax.Array,
         *, interpret: bool = False) -> jax.Array:
    """y = A @ x for one x: (ct*bn,) — the batched kernel at B = 1."""
    return spmv_batched(data, idx, x[None], interpret=interpret)[0]


def spmv_dot(data: jax.Array, idx: jax.Array, x: jax.Array,
             *, interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(A @ x, xᵀAx) for one x — the batched kernel at B = 1."""
    y, d = spmv_dot_batched(data, idx, x[None], interpret=interpret)
    return y[0], d[0]
