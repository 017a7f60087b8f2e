"""Fused PCG vector-update Pallas kernel (beyond-paper optimization).

Lines 4-7 of the paper's Alg. 1 are four memory-bound vector passes:
  x' = x + a p;  r' = r - a q;  z' = P r' (block-Jacobi);  rz' = r'.z'
Unfused that is ~10 vector reads + 4 writes of HBM traffic per iteration;
here it is one read of x, r, p, q and the preconditioner, one write of
x', r', z', and one more read of r', z' for the dot.

Layout. Vectors are viewed as (M/L, L) rows of L lanes and the grid runs
over row blocks of ``rows`` elements (``rows/L`` sublane rows). On the chip
L = 128 and ``rows/L`` is a multiple of 8, which is what Mosaic's (8, 128)
block rule asks; the interpreter accepts any L that the preconditioner
block b divides. α lives in SMEM.

Block-Jacobi in lanes. With b | L no block straddles a row, and the
block-diagonal P is stored by diagonals (``jacobi_diagonals``):

  z[l] = Σ_{d=-(b-1)}^{b-1} D_d[l] · r[l + d]      (same block only)

Each term is one diagonal times a lane rotation of r', masked to the lanes
whose block holds l + d — elementwise VPU work, no in-kernel reshape, and a
NaN in one block cannot leak into its neighbours through 0·NaN.

r'.z' is reduced after the kernel from its outputs (``rz_dot``), one more
read of r' and z'. ``ref.fused_pcg_update_seq_ref`` mirrors every step of
this, so the jnp backend is bit-identical in f64. The mirroring has to be
structural: XLA:CPU removes ``optimization_barrier`` before fusion and
then contracts a multiply that feeds an add into an FMA, so a barrier
cannot pin a product; the same expression shape in both paths is what
keeps them equal.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import i32

LANES = 128
ROW_TARGET = 256 * LANES     # elements per grid step on the chip geometry


def lanes_for(m: int, block: int) -> int:
    """Lane width L of the (M/L, L) vector view: the TPU's 128 when the
    preconditioner block divides it and M fills whole rows, else one block
    per row (legal for the interpreter; the chip geometry never needs it)."""
    return LANES if LANES % block == 0 and m % LANES == 0 else block


def update_rows(m: int, block: int) -> int:
    """Elements per grid step: a multiple of the lane width whose sublane
    count is a multiple of 8 (or the whole vector) and that divides M, as
    large as possible up to ``ROW_TARGET``."""
    if m % block:
        raise ValueError(f"M={m} not divisible by precond block {block}")
    lanes = lanes_for(m, block)
    n_rows = m // lanes
    best = None
    for r in range(8, n_rows + 1, 8):
        if n_rows % r == 0 and r * lanes <= ROW_TARGET:
            best = r
    if best is None:
        best = n_rows        # one step over the whole vector
    return best * lanes


def jacobi_diagonals(pinv_blocks, lanes: int):
    """(M/b, b, b) inverse blocks -> (2b-1, M/L, L) diagonals D_d with
    D_d[n*b + i] = P_n[i, i+d] (0 where i+d leaves the block)."""
    nb, b, _ = pinv_blocks.shape
    i = np.arange(b)
    diags = []
    for d in range(-(b - 1), b):
        j = i + d
        ok = (j >= 0) & (j < b)
        vals = pinv_blocks[:, i, np.clip(j, 0, b - 1)]
        diags.append(jnp.where(ok, vals, 0).reshape(-1, lanes))
    return jnp.stack(diags)


def lane_block_mask(shape, block: int, d: int):
    """Lanes l whose block also holds lane l + d. Every operand is int32:
    Mosaic cannot lower the int64 arithmetic that Python ints become under
    ``jax_enable_x64``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    pos = jax.lax.rem(lane, np.int32(block))
    return (pos >= np.int32(-d)) & (pos < np.int32(block - d))


def _lane_roll(v, shift: int):
    return pltpu.roll(v, np.int32(shift), v.ndim - 1)


def block_apply(dia, r, block: int, roll=_lane_roll):
    """z = Σ_d D_d · r[l + d] over (rows, L) lanes, d ascending. ``dia`` is
    indexed per diagonal (an array or a kernel ref); ``roll(v, s)`` rotates
    lanes like ``jnp.roll`` (the kernel's ``pltpu.roll`` has no eager
    rule). The mask zeroes r's lanes outside the block before the product,
    so every term is one multiply feeding one add — the shape in which
    XLA:CPU contracts the interpreter and the reference alike."""
    lanes = r.shape[-1]
    z = None
    for k, d in enumerate(range(-(block - 1), block)):
        shifted = r if d == 0 else roll(r, (-d) % lanes)
        term = dia[k] * jnp.where(lane_block_mask(r.shape, block, d),
                                  shifted, 0)
        z = term if z is None else z + term
    return z


def _update_tile(a, x, r, p, q, dia, block: int, roll=_lane_roll):
    x_new = x + a * p
    r_new = r - a * q
    return x_new, r_new, block_apply(dia, r_new, block, roll)


def _fused_kernel(alpha_ref, x_ref, r_ref, p_ref, q_ref, dia_ref,
                  xo_ref, ro_ref, zo_ref, *, block):
    xo_ref[0], ro_ref[0], zo_ref[0] = _update_tile(
        alpha_ref[pl.program_id(0)], x_ref[0], r_ref[0], p_ref[0], q_ref[0],
        dia_ref, block)


def _geometry(m: int, dia, rows: int):
    nd, _, lanes = dia.shape
    block = (nd + 1) // 2
    if m % rows or rows % lanes or lanes % block:
        raise ValueError(f"rows={rows} must divide M={m} and be a multiple "
                         f"of the lane width {lanes}, itself a multiple of "
                         f"the precond block {block}")
    return block, lanes, rows // lanes, m // rows


def rz_dot(r, z, rows: int):
    """r·z of one (M,) member in a fixed association: per ``rows`` block,
    the (8, L) sublane groups added in order, then the block sums, then
    the sum over blocks. It runs on the kernel's outputs, outside the
    kernel: a product folded in-kernel is an FMA candidate that XLA:CPU
    contracts differently from one program to the next, and a barrier
    cannot stop it (the CPU pipeline removes barriers before fusion)."""
    lanes = LANES if rows % LANES == 0 else rows
    sub = rows // lanes
    g = 8 if sub % 8 == 0 else 1
    prod = (r * z).reshape(-1, sub, lanes)
    acc = prod[:, :g]
    for i in range(g, sub, g):
        acc = acc + prod[:, i:i + g]
    per_block = jax.lax.optimization_barrier(jnp.sum(acc, axis=(1, 2)))
    return jnp.sum(per_block)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def fused_pcg_update(alpha: jax.Array, x: jax.Array, r: jax.Array,
                     p: jax.Array, q: jax.Array, dia: jax.Array,
                     *, rows: int, interpret: bool = False):
    """Returns (x', r', z', rz') with rz' = r'.z' fully reduced.

    x, r, p, q: (M,); dia: ``jacobi_diagonals(pinv_blocks, L)``; alpha:
    scalar. ``rows`` is the per-grid-step element count (``update_rows``).
    This is the batched kernel at B = 1, so a member of a batch and a lone
    solve run one program."""
    out = fused_pcg_update_batched(
        alpha.reshape(1), *(v[None] for v in (x, r, p, q)), dia, rows=rows,
        interpret=interpret)
    return tuple(o[0] for o in out)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def fused_pcg_update_batched(alpha: jax.Array, x: jax.Array, r: jax.Array,
                             p: jax.Array, q: jax.Array, dia: jax.Array,
                             *, rows: int, interpret: bool = False):
    """Batched fused update: one kernel pass advances all B members.

    alpha: (B,); x, r, p, q: (B, M); dia shared across the batch. Grid
    (B, M/rows): cell (b, i) updates member b's i-th row block, and each
    member's r'·z' is reduced on its own, so member results are
    bit-identical to B separate calls. Returns (x', r', z') as (B, M) and
    rz' as (B,)."""
    nbatch, m = x.shape
    block, lanes, sub, grid = _geometry(m, dia, rows)
    vec = pl.BlockSpec((1, sub, lanes), lambda bi, i: i32(bi, i, 0))
    xo, ro, zo = pl.pallas_call(
        functools.partial(_fused_kernel, block=block),
        grid=(nbatch, grid),
        in_specs=[pl.BlockSpec((nbatch,), lambda bi, i: i32(0),
                               memory_space=pltpu.SMEM),
                  vec, vec, vec, vec,
                  pl.BlockSpec((dia.shape[0], sub, lanes),
                               lambda bi, i: i32(0, i, 0))],
        out_specs=(vec, vec, vec),
        out_shape=(jax.ShapeDtypeStruct((nbatch, m // lanes, lanes),
                                        x.dtype),) * 3,
        interpret=interpret,
    )(alpha, *(v.reshape(nbatch, -1, lanes) for v in (x, r, p, q)), dia)
    xo, ro, zo = (v.reshape(nbatch, m) for v in (xo, ro, zo))
    return xo, ro, zo, jnp.stack([rz_dot(ro[i], zo[i], rows)
                                  for i in range(nbatch)])
