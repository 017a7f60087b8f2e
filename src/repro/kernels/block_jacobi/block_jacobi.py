"""Block-Jacobi preconditioner apply: z = blockdiag(P_1..P_nb) r.

The fused update's block apply (``kernels.fused_pcg.block_apply``) as a
kernel of its own: r is viewed as (M/L, L) lanes, P is stored by its 2b-1
diagonals, and each grid step applies them to one row block — the layout
that compiles for the chip. Used standalone by the preconditioner's apply
and the SSOR kernel's middle pass; the main loop fuses the same
computation into ``kernels.fused_pcg``.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from repro.kernels import i32
from repro.kernels.fused_pcg.fused_pcg import (_geometry, block_apply,
                                               jacobi_diagonals, lanes_for,
                                               update_rows)


def _bj_kernel(dia_ref, r_ref, o_ref, *, block):
    o_ref[...] = block_apply(dia_ref, r_ref[...], block)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def block_jacobi_apply(pinv_blocks: jax.Array, r: jax.Array,
                       *, rows: int | None = None, interpret: bool = False):
    """pinv_blocks: (M/b, b, b); r: (M,) -> z: (M,). ``rows`` is the
    per-grid-step element count (default ``update_rows``)."""
    m = r.shape[0]
    b = pinv_blocks.shape[-1]
    rows = rows or update_rows(m, b)
    dia = jacobi_diagonals(pinv_blocks, lanes_for(rows, b))
    block, lanes, sub, grid = _geometry(m, dia, rows)
    vec = pl.BlockSpec((sub, lanes), lambda i: i32(i, 0))
    return pl.pallas_call(
        functools.partial(_bj_kernel, block=block),
        grid=(grid,),
        in_specs=[pl.BlockSpec((dia.shape[0], sub, lanes),
                               lambda i: i32(0, i, 0)), vec],
        out_specs=vec,
        out_shape=jax.ShapeDtypeStruct((m // lanes, lanes), r.dtype),
        interpret=interpret,
    )(dia, r.reshape(-1, lanes)).reshape(m)
