"""Block-ELL sparse storage: the TPU-native adaptation of the paper's CSR SpMV.

The matrix is tiled into dense (bm x bn) blocks. Every *row tile* stores a
fixed number ``kmax`` of column tiles (dense data + int32 column-tile index),
padded with explicit zero tiles pointing at column-tile 0. This trades a bit
of padding for:

  * MXU-aligned dense (bm x bn) @ (bn,) products instead of scalar CSR
    traversal (the GSL path the paper uses on CPUs),
  * a static shape that `jax.jit`/Pallas can tile over, and
  * a per-row-tile gather of x blocks that maps 1:1 onto a Pallas
    scalar-prefetch ``BlockSpec`` index_map (see ``repro.kernels.spmv``).

Construction happens host-side in numpy (static data in the paper's sense —
it can be "retrieved from safe storage" after a failure).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.sparse.partition import Partition


def ell_matvec(data: jax.Array, idx: jax.Array, x: jax.Array) -> jax.Array:
    """y = A x for Block-ELL (data, idx): gather the x tiles, one einsum."""
    xb = x.reshape(-1, data.shape[-1])                # (col_tiles, bn)
    gathered = xb[idx]                                # (rt, kmax, bn)
    return jnp.einsum("rkij,rkj->ri", data, gathered).reshape(-1)


@dataclasses.dataclass
class BlockEll:
    """Block-ELL matrix.

    data: (row_tiles, kmax, bm, bn)  dense tile values (zero tiles pad).
    idx:  (row_tiles, kmax) int32    column-tile index per slot (0 pads).
    nblk: (row_tiles,) int32         number of valid slots per row tile.
    shape: (M, M)
    """

    data: jax.Array
    idx: jax.Array
    nblk: jax.Array
    shape: tuple[int, int]
    bm: int
    bn: int

    @property
    def row_tiles(self) -> int:
        return self.data.shape[0]

    @property
    def kmax(self) -> int:
        return self.data.shape[1]

    @property
    def dtype(self):
        return self.data.dtype

    # ------------------------------------------------------------------ #
    @staticmethod
    def from_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 m: int, bm: int, bn: int, kmax: Optional[int] = None,
                 dtype=np.float64) -> "BlockEll":
        if m % bm or m % bn:
            raise ValueError(f"M={m} must be divisible by bm={bm} and bn={bn}")
        rows = np.asarray(rows, np.int64)
        cols = np.asarray(cols, np.int64)
        vals = np.asarray(vals, dtype)
        rt, ct = rows // bm, cols // bn
        n_row_tiles = m // bm
        # unique (row_tile, col_tile) pairs, slot numbering per row tile
        key = rt * (m // bn) + ct
        uniq, inv = np.unique(key, return_inverse=True)
        urt, uct = uniq // (m // bn), uniq % (m // bn)
        counts = np.bincount(urt, minlength=n_row_tiles)
        needed = int(counts.max()) if counts.size else 1
        if kmax is None:
            kmax = max(needed, 1)
        elif needed > kmax:
            raise ValueError(f"kmax={kmax} < max tiles/row-tile {needed}")
        # slot index of each unique tile within its row tile (uniq sorted => ct ascending)
        starts = np.zeros(n_row_tiles + 1, np.int64)
        np.cumsum(counts, out=starts[1:])
        slot_of_uniq = np.arange(uniq.size) - starts[urt]
        data = np.zeros((n_row_tiles, kmax, bm, bn), dtype)
        idx = np.zeros((n_row_tiles, kmax), np.int32)
        idx[urt, slot_of_uniq] = uct.astype(np.int32)
        # scatter values into dense tiles
        u = inv                      # which unique tile each nnz belongs to
        np.add.at(data, (rt, slot_of_uniq[u], rows % bm, cols % bn), vals)
        nblk = counts.astype(np.int32)
        return BlockEll(jnp.asarray(data), jnp.asarray(idx), jnp.asarray(nblk),
                        (m, m), bm, bn)

    @staticmethod
    def from_dense(a: np.ndarray, bm: int, bn: int,
                   kmax: Optional[int] = None) -> "BlockEll":
        a = np.asarray(a)
        rows, cols = np.nonzero(a)
        return BlockEll.from_coo(rows, cols, a[rows, cols], a.shape[0], bm, bn,
                                 kmax=kmax, dtype=a.dtype)

    # ------------------------------------------------------------------ #
    def to_dense(self) -> np.ndarray:
        m = self.shape[0]
        out = np.zeros((m, m), self.data.dtype)
        data = np.asarray(self.data)
        idx = np.asarray(self.idx)
        nblk = np.asarray(self.nblk)
        for r in range(self.row_tiles):
            for k in range(int(nblk[r])):
                c = int(idx[r, k])
                out[r * self.bm:(r + 1) * self.bm,
                    c * self.bn:(c + 1) * self.bn] += data[r, k]
        return out

    def matvec(self, x: jax.Array) -> jax.Array:
        """Reference jnp SpMV (the oracle; kernels/spmv accelerates this)."""
        return ell_matvec(self.data, self.idx, x)

    # -- partition-aware views ---------------------------------------- #
    def node_slice(self, part: Partition, s: int) -> "BlockEll":
        """Row tiles owned by node s (a (R x M) strip, still Block-ELL)."""
        rpt = part.row_tiles_per_node
        sl = slice(s * rpt, (s + 1) * rpt)
        return BlockEll(self.data[sl], self.idx[sl], self.nblk[sl],
                        (part.rows_per_node, self.shape[1]), self.bm, self.bn)

    def needed_col_tiles(self, part: Partition) -> list[np.ndarray]:
        """For each node l: sorted unique global column tiles its rows touch.

        This is the tile-granular analogue of the paper's sets ``I_{s,l}``
        (restricted to what l *receives*): the owner of tile t must send t to
        every node whose rows reference it.
        """
        idx = np.asarray(self.idx)
        nblk = np.asarray(self.nblk)
        valid = np.arange(self.kmax)[None, :] < nblk[:, None]
        out = []
        rpt = part.row_tiles_per_node
        for l in range(part.n_nodes):
            sl = slice(l * rpt, (l + 1) * rpt)
            t = idx[sl][valid[sl]]
            out.append(np.unique(t))
        return out
