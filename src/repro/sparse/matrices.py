"""SPD test-problem generators (paper §5 uses SuiteSparse; offline we generate
problems of the same regime — elliptic-PDE discretizations and banded SPD).

All generators return COO triples (host numpy). ``build_problem`` packages a
generator output into the distributed ``Problem`` used by the solvers: the
Block-ELL matrix, the partition, the right-hand side, a registered
preconditioner from ``repro.precond`` (block-Jacobi by default; SSOR /
Chebyshev / IC(0) via ``precond=...``), and the raw COO (the "static data in
safe storage" that the paper assumes replacement nodes can reload after a
failure — Alg. 2 line 1).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.blockell import BlockEll
from repro.sparse.partition import Partition


# --------------------------------------------------------------------------- #
# generators: COO triples for SPD matrices
# --------------------------------------------------------------------------- #
def poisson2d(nx: int, ny: Optional[int] = None):
    """5-point Laplacian on an nx x ny grid (Dirichlet). SPD, bandwidth nx."""
    ny = ny or nx
    n = nx * ny
    i = np.arange(n)
    x, y = i % nx, i // nx
    rows, cols, vals = [i], [i], [np.full(n, 4.0)]
    for (dx, dy) in ((1, 0), (-1, 0), (0, 1), (0, -1)):
        ok = (0 <= x + dx) & (x + dx < nx) & (0 <= y + dy) & (y + dy < ny)
        rows.append(i[ok]); cols.append(i[ok] + dx + dy * nx)
        vals.append(np.full(ok.sum(), -1.0))
    return np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), n


def poisson3d(nx: int, ny: Optional[int] = None, nz: Optional[int] = None,
              eps: float = 1.0):
    """7-point Laplacian on an nx x ny x nz grid. SPD, bandwidth nx*ny.
    ``eps`` < 1 makes the y/z couplings anisotropic (harder for block-Jacobi
    — more PCG iterations, the regime of the paper's structural matrices)."""
    ny = ny or nx
    nz = nz or nx
    n = nx * ny * nz
    i = np.arange(n)
    x = i % nx
    y = (i // nx) % ny
    z = i // (nx * ny)
    rows, cols, vals = [i], [i], [np.full(n, 2.0 + 4.0 * eps)]
    for (dx, dy, dz) in ((1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0),
                         (0, 0, 1), (0, 0, -1)):
        ok = ((0 <= x + dx) & (x + dx < nx) & (0 <= y + dy) & (y + dy < ny)
              & (0 <= z + dz) & (z + dz < nz))
        w = -1.0 if dx else -eps
        rows.append(i[ok]); cols.append(i[ok] + dx + dy * nx + dz * nx * ny)
        vals.append(np.full(ok.sum(), w))
    return np.concatenate(rows), np.concatenate(cols), np.concatenate(vals), n


def banded_spd(n: int, bandwidth: int, density: float = 0.5, seed: int = 0,
               shift: float = 0.1):
    """Random symmetric banded matrix made SPD by diagonal dominance.

    Mimics the denser-band structural matrices (audikw_1 regime): entries
    within ``bandwidth`` of the diagonal with probability ``density``.
    """
    rng = np.random.default_rng(seed)
    rows_l, cols_l, vals_l = [], [], []
    for off in range(1, bandwidth + 1):
        m = n - off
        mask = rng.random(m) < density
        i = np.arange(m)[mask]
        v = rng.standard_normal(i.size)
        rows_l += [i, i + off]
        cols_l += [i + off, i]
        vals_l += [v, v]
    rows = np.concatenate(rows_l) if rows_l else np.empty(0, np.int64)
    cols = np.concatenate(cols_l) if cols_l else np.empty(0, np.int64)
    vals = np.concatenate(vals_l) if vals_l else np.empty(0)
    # diagonal dominance => SPD
    abssum = np.zeros(n)
    np.add.at(abssum, rows, np.abs(vals))
    rows = np.concatenate([rows, np.arange(n)])
    cols = np.concatenate([cols, np.arange(n)])
    vals = np.concatenate([vals, abssum + shift])
    return rows, cols, vals, n


# --------------------------------------------------------------------------- #
# preconditioners live in repro.precond (registry + jacobi/ssor/chebyshev/
# ic0); the block-Jacobi block extraction and Cholesky-based inverse are
# re-exported here for backward compatibility with the seed API.
# --------------------------------------------------------------------------- #
from repro.precond.jacobi import (block_jacobi_blocks,   # noqa: F401, E402
                                  invert_blocks)


# --------------------------------------------------------------------------- #
# problem container
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Problem:
    """A distributed SPD system Ax = b plus its preconditioner.

    ``coo`` is retained host-side: it is the paper's "static data in safe
    storage" from which replacement nodes rebuild ``A_{I_f,I}``, ``P_{I_f,*}``
    and ``b_{I_f}`` during reconstruction (Alg. 2 line 1).
    """

    a: BlockEll
    part: Partition
    b: jax.Array
    pinv_blocks: jax.Array        # (M/b, b, b) inverted block-Jacobi blocks
    diag_blocks: jax.Array        # (M/b, b, b) raw A diagonal blocks (= P^-1)
    precond_block: int
    coo: tuple[np.ndarray, np.ndarray, np.ndarray]
    precond: object = None        # repro.precond.Preconditioner (None/"jacobi"
    #                               keeps the seed block-Jacobi fast paths)

    @property
    def m(self) -> int:
        return self.part.m

    @property
    def precond_name(self) -> str:
        return "jacobi" if self.precond is None else self.precond.name

    def apply_precond(self, r: jax.Array) -> jax.Array:
        """z = P r (jnp reference backend).

        Block-Jacobi keeps the seed's einsum over ``self.pinv_blocks`` —
        bit-identical to the pre-subsystem path and sharding-aware (the
        arrays are re-placed by ``comm.shard.place_problem``); other
        preconditioners delegate to their registered apply."""
        if self.precond is None or self.precond.name == "jacobi":
            from repro.core.ops import jacobi_apply
            return jacobi_apply(self.pinv_blocks, r)
        return self.precond.apply(r, backend="jnp")

    def solver_ops(self, backend: str = "auto", batch: int = 0,
                   fused: bool = False):
        """The SolverOps execution bundle for this problem (see
        repro.core.ops). Cached per (backend, batch, fused): the bundle
        holds the block-Jacobi diagonals it derives from the problem.

        backend: "auto" (pallas on TPU, jnp elsewhere) | "jnp" | "pallas" |
        "interpret". On a TPU, "pallas" and "auto" refuse float64 data
        with a ValueError (see ``repro.kernels.resolve_backend``).
        ``batch`` > 0 returns the batched bundle whose ops carry a leading
        B axis (one dispatch advances B members);
        ``fused=True`` picks its throughput mode (fused-batched einsums,
        per-member ~ulp instead of bit-identical — see core.ops)."""
        from repro.core.ops import make_problem_ops
        from repro.kernels import resolve_backend
        backend = resolve_backend(backend, self.b.dtype)
        cache = getattr(self, "_ops_cache", None)
        if cache is None:
            cache = {}
            self._ops_cache = cache
        key = (backend, batch, fused)
        if key not in cache:
            cache[key] = make_problem_ops(self, backend, batch=batch,
                                          fused=fused)
        return cache[key]

    def submatrix_coo(self, row_lo: int, row_hi: int, col_lo: int, col_hi: int):
        """COO of A[row_lo:row_hi, col_lo:col_hi] (for A_ff / inner solves)."""
        rows, cols, vals = self.coo
        ok = (rows >= row_lo) & (rows < row_hi) & (cols >= col_lo) & (cols < col_hi)
        return rows[ok] - row_lo, cols[ok] - col_lo, vals[ok]


def build_problem(kind: str, n_nodes: int, *, bm: int = 8, bn: int = 8,
                  precond_block: int = 10, dtype=np.float64, seed: int = 0,
                  precond: str = "jacobi", precond_opts: dict | None = None,
                  **kw) -> Problem:
    """Build a distributed SPD problem.

    kind: "poisson2d" (nx[, ny]) | "poisson3d" (nx[, ny, nz]) |
          "banded" (n, bandwidth[, density]).

    ``precond`` selects a registered preconditioner ("jacobi" | "ssor" |
    "chebyshev" | "ic0"); ``precond_opts`` passes options through to its
    builder (e.g. omega=1.2 for SSOR, degree=6 for Chebyshev). The
    block-Jacobi diagonal/inverse blocks are always built — they also serve
    as the Alg. 2 line-8 inner-solve preconditioner.

    ``precond_opts={"node_local": True}`` builds the additive-Schwarz
    variant of SSOR/IC(0): the preconditioner sees only the COO entries
    whose row and column are owned by the same node, so its sweeps restrict
    to each node's diagonal slab and partition over the "nodes" mesh axis
    (``comm.shard`` runs them embarrassingly parallel). A no-op for
    block-Jacobi (its blocks never straddle node boundaries); rejected for
    Chebyshev, whose sharded apply distributes through the SpMV instead.

    The problem size is padded (with identity rows) up to
    lcm(n_nodes*bm, n_nodes*bn, n_nodes*precond_block) multiples so that the
    partition constraints hold; padding rows are decoupled (A_ii=1, b_i=0) and
    do not perturb the solution of the original system.
    """
    if kind == "poisson2d":
        rows, cols, vals, m = poisson2d(**kw)
    elif kind == "poisson3d":
        rows, cols, vals, m = poisson3d(**kw)
    elif kind == "banded":
        rows, cols, vals, m = banded_spd(seed=seed, **kw)
    else:
        raise ValueError(f"unknown problem kind {kind!r}")

    unit = n_nodes * int(np.lcm.reduce([bm, bn, precond_block]))
    m_pad = ((m + unit - 1) // unit) * unit
    if m_pad != m:
        pad = np.arange(m, m_pad)
        rows = np.concatenate([rows, pad])
        cols = np.concatenate([cols, pad])
        vals = np.concatenate([vals, np.ones(pad.size)])
    vals = vals.astype(dtype)

    part = Partition(m=m_pad, n_nodes=n_nodes, bm=bm, bn=bn)
    a = BlockEll.from_coo(rows, cols, vals, m_pad, bm, bn, dtype=dtype)
    diag = block_jacobi_blocks(rows, cols, vals, m_pad, precond_block, dtype)
    pinv = invert_blocks(diag)
    from repro import precond as precond_pkg
    opts = dict(precond_opts or {})
    node_local = bool(opts.pop("node_local", False))
    pc_coo = (rows, cols, vals)
    if node_local and precond not in ("jacobi",):
        if precond == "chebyshev":
            raise ValueError(
                "node_local does not apply to chebyshev — its sharded apply "
                "distributes through the SpMV (comm.shard)")
        keep = part.intra_node_mask(rows, cols)
        pc_coo = (rows[keep], cols[keep], vals[keep])
    pc = precond_pkg.build(precond, coo=pc_coo, m=m_pad,
                           block=precond_block, dtype=dtype, a=a,
                           diag_blocks=diag, pinv_blocks=pinv,
                           **opts)
    rng = np.random.default_rng(seed + 1)
    b = rng.standard_normal(m_pad).astype(dtype)
    if m_pad != m:
        b[m:] = 0.0
    return Problem(a=a, part=part, b=jnp.asarray(b),
                   pinv_blocks=jnp.asarray(pinv), diag_blocks=jnp.asarray(diag),
                   precond_block=precond_block, coo=(rows, cols, vals),
                   precond=pc)
