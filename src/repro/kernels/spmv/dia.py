"""SpMV (and SpMV+dot) over an operator stored by diagonals — Pallas TPU.

The operator is ``sparse.dia.Diagonals``: one (M/L, L) value array per
offset d, row-aligned, so that

  y[g] = Σ_d D_d[g] · x[g + d]          (g the flat row, d ascending)

Every term is elementwise VPU work: no tile padding, no MXU. On the (M/L, L)
view of x, x[g + d] for d = q·L + s (0 <= s < L) is row r + q at lane
l + s, spilling into row r + q + 1 where l + s >= L: a lane roll of both
rows and a select of the lanes that spill.

Grid (B, M/(rows·L)): step (b, i) computes ``rows`` sublane rows of member
b's y. x arrives as the step's own block plus one ``halo``-row block on each
side (the neighbouring blocks' edges, zeroed beyond the matrix), copied into
one VMEM window, so a call reads x once plus 2·halo/rows. The diagonals'
blocks are shared across members. The body loops over ``chunk`` rows at a
time (a loop, not unrolled: the kernel is lowered anew in every program
that holds it, and an unrolled body made that cost seconds a process); each
term is one multiply feeding one add, d ascending, as
``ref.dia_terms`` adds them, so that the jnp and interpret backends agree
bit for bit in f64.

The dot's products x⊙y are formed at the flush, while y is in VMEM, and
leave as lane rows; the caller reduces them in a pinned association
(``reduce_products``, mirrored by ``ref.spmv_dia_dot_ref``). A product
summed in the kernel would be an FMA candidate that XLA:CPU contracts by
the context the interpreted kernel lands in, which breaks the bit-identity
with the mirror.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import i32
from repro.sparse.dia import LANES

VMEM_BUDGET = 12 * 2 ** 20     # bytes of one step's buffers, double-buffered
MAX_DIAGONALS = 32             # the body unrolls one term per diagonal; a
#                                27-point stencil has 27
CHUNK = 112                    # rows the body's loop computes at a time, at
#                                most (a multiple of 8 that divides ``rows``):
#                                a 112³ SpMV+dot on a v5e took 67.8 µs at 112
#                                rows, 68.4 at 56, 97.4 at 16, 153.6 at 8


@dataclasses.dataclass(frozen=True)
class DiaLayout:
    """How the kernel walks one operator: its static arguments."""
    offsets: tuple[int, ...]   # the diagonals' offsets, ascending
    rows: int      # sublane rows of y per grid step
    halo: int      # rows of x fetched on each side of them
    chunk: int     # rows the body computes at a time


def halo_rows(offsets, lanes: int) -> int:
    """Rows of x beyond its own that a row of y reads, on either side."""
    need = 0
    for d in offsets:
        q, s = divmod(d, lanes)
        need = max(need, -q, q + (s > 0))
    return need


def dia_layout(n_rows: int, lanes: int, offsets, itemsize: int, *,
               mosaic: bool, budget: int = VMEM_BUDGET) -> DiaLayout | None:
    """The kernel's blocking of an (n_rows, lanes) vector view, or None when
    the operator does not fit it: more than ``MAX_DIAGONALS`` diagonals, an
    x window (its halo) too large for VMEM, or, with ``mosaic``, a view
    that the chip's compiler cannot block. ``mosaic`` keeps to Mosaic's
    (8, 128) rule: 128 lanes and blocks of a multiple of 8 rows, so n_rows
    has to be one; the interpreter takes blocks of any rows, multiples of 8
    where n_rows is. The halo is the least such divisor of n_rows covering
    the offsets, and the step as many rows as fit ``budget`` bytes."""
    if len(offsets) > MAX_DIAGONALS:
        return None
    if mosaic and (lanes != LANES or n_rows % 8):
        return None
    sub = 8 if n_rows % 8 == 0 else 1
    divs = [r for r in range(sub, n_rows + 1, sub) if n_rows % r == 0]
    need = max(halo_rows(offsets, lanes), 1)
    halo = next((h for h in divs if h >= need), None)
    if halo is None:
        return None
    row_bytes = lanes * itemsize
    # double-buffered: the diagonals, x's own rows, y and the products; the
    # two halos; plus the single window scratch
    def fits(r):
        return (2 * (len(offsets) + 3) * r + 4 * halo + r + 2 * halo) \
            * row_bytes <= budget
    rows = [r for r in divs if r % halo == 0 and fits(r)]
    if not rows:
        return None
    # two steps or more where the halo allows: an interpreted kernel whose
    # grid has one step is inlined into its caller, where XLA:CPU contracts
    # its multiply-adds differently than in the grid loop
    rows = max([r for r in rows if 2 * r <= n_rows] or rows)
    chunk = max((c for c in range(8, CHUNK + 1, 8) if rows % c == 0),
                default=rows)
    return DiaLayout(tuple(int(d) for d in offsets), rows, halo, chunk)


def _lane_roll(v, shift: int):
    return pltpu.roll(v, np.int32(shift), v.ndim - 1)


def _chunk_terms(dia_ref, win_ref, lo, lay: DiaLayout, lanes):
    """y rows [lo, lo + chunk) of the step and x's own rows there:
    Σ_d D_d · x[g + d], d ascending. ``win_ref`` row ``halo + r`` holds x
    row r of the step."""
    n = lay.chunk
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1)
    loads = {}

    def x_rows(q):
        if q not in loads:
            loads[q] = win_ref[pl.ds(lo + np.int32(lay.halo + q), n), :]
        return loads[q]

    y = None
    for k, d in enumerate(lay.offsets):
        q, s = divmod(d, lanes)
        xd = x_rows(q)
        if s:
            shift = lanes - s
            xd = jnp.where(lane < np.int32(shift), _lane_roll(xd, shift),
                           _lane_roll(x_rows(q + 1), shift))
        term = dia_ref[k, pl.ds(lo, n), :] * xd
        y = term if y is None else y + term
    return y, x_rows(0)


def _dia_kernel(dia_ref, xp_ref, xm_ref, xn_ref, *refs, lay, with_dot):
    if with_dot:
        y_ref, prod_ref, win_ref = refs
    else:
        y_ref, win_ref = refs
    i = pl.program_id(1)
    h, rows, n = lay.halo, lay.rows, lay.chunk
    lanes = win_ref.shape[-1]
    win_ref[pl.ds(0, h), :] = jnp.where(i > 0, xp_ref[0], 0)
    win_ref[pl.ds(h, rows), :] = xm_ref[0]
    win_ref[pl.ds(h + rows, h), :] = jnp.where(
        i < pl.num_programs(1) - 1, xn_ref[0], 0)

    def chunk(_, lo):
        lo = pl.multiple_of(lo, n)
        y, x = _chunk_terms(dia_ref, win_ref, lo, lay, lanes)
        y_ref[0, pl.ds(lo, n), :] = y
        if with_dot:
            prod_ref[0, pl.ds(lo, n), :] = y * x
        return lo + np.int32(n)

    jax.lax.fori_loop(0, rows // n, chunk, np.int32(0))


def reduce_products(prod):
    """(R, L) products x⊙y of one member -> x·y: per lane row, then over
    the rows (the association ``ref.spmv_dia_dot_ref`` repeats)."""
    partial = jax.lax.optimization_barrier(jnp.sum(prod, axis=1))
    return jnp.sum(partial)


def _call(data, x, lay: DiaLayout, with_dot: bool, interpret):
    nd, n_rows, lanes = data.shape
    nb = x.shape[0]
    h, rows = lay.halo, lay.rows
    per, last = rows // h, n_rows // h - 1
    steps = n_rows // rows
    own = pl.BlockSpec((1, rows, lanes), lambda b, i: i32(b, i, 0))
    in_specs = [
        pl.BlockSpec((nd, rows, lanes), lambda b, i: i32(0, i, 0)),
        pl.BlockSpec((1, h, lanes),
                     lambda b, i: i32(b, jnp.maximum(i * per - 1, 0), 0)),
        own,
        pl.BlockSpec((1, h, lanes),
                     lambda b, i: i32(b, jnp.minimum((i + 1) * per, last),
                                      0)),
    ]
    n_out = 2 if with_dot else 1
    xv = x.reshape(nb, n_rows, lanes)
    return pl.pallas_call(
        functools.partial(_dia_kernel, lay=lay, with_dot=with_dot),
        grid=(nb, steps),
        in_specs=in_specs,
        out_specs=(own,) * n_out,
        out_shape=(jax.ShapeDtypeStruct(xv.shape, data.dtype),) * n_out,
        scratch_shapes=[pltpu.VMEM((rows + 2 * h, lanes), data.dtype)],
        interpret=interpret,
        name="spmv_dot" if with_dot else "spmv",
    )(data, xv, xv, xv)


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def spmv_dia_batched(data: jax.Array, x: jax.Array, *, layout: DiaLayout,
                     interpret: bool = False) -> jax.Array:
    """data: (n_diag, M/L, L) diagonals at ``layout.offsets``; x: (B, M).
    Returns y with y[b] = A @ x[b], (B, M): one pass for all B members."""
    (y,) = _call(data, x, layout, False, interpret)
    return y.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("layout", "interpret"))
def spmv_dia_dot_batched(data: jax.Array, x: jax.Array, *,
                         layout: DiaLayout, interpret: bool = False
                         ) -> tuple[jax.Array, jax.Array]:
    """Fused y = A @ x and xᵀy for B members in one kernel pass. Returns
    (y: (B, M), xᵀy: (B,)), each member reduced on its own."""
    y, prod = _call(data, x, layout, True, interpret)
    return (y.reshape(x.shape),
            jnp.stack([reduce_products(p) for p in prod]))


def spmv_dia(data: jax.Array, x: jax.Array, *, layout: DiaLayout,
             interpret: bool = False) -> jax.Array:
    """y = A @ x for one x: (M,) — the batched kernel at B = 1."""
    return spmv_dia_batched(data, x[None], layout=layout,
                            interpret=interpret)[0]


def spmv_dia_dot(data: jax.Array, x: jax.Array, *, layout: DiaLayout,
                 interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(A @ x, xᵀAx) for one x — the batched kernel at B = 1."""
    y, d = spmv_dia_dot_batched(data, x[None], layout=layout,
                                interpret=interpret)
    return y[0], d[0]
