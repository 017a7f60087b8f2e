"""Storage by diagonals: the operator as one value array per distinct offset.

A stencil operator has a handful of diagonals (the 3D 7-point Laplacian on
an nx×ny×nz grid: offsets 0, ±1, ±nx, ±nx·ny). Stored by diagonals it keeps
one offset per diagonal and no index per value, and its SpMV is elementwise
work over lanes (``repro.kernels.spmv.dia``). Block-ELL tiles hold the same
diagonals padded to dense bm×bn tiles, at most 1/max(bm, bn) full.

Layout: ``data[k]`` is the diagonal of offset ``offsets[k]`` (ascending),
row-aligned — ``data[k][i] = A[i, i + offsets[k]]``, 0 where ``i +
offsets[k]`` falls outside the matrix — viewed as (M/L, L) rows of L lanes,
the fused update's vector view. Built on the host from the COO.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def dia_lanes(m: int) -> int:
    """Lane width L of the (M/L, L) view: the TPU's 128 when M fills whole
    rows, else the largest width that divides M (the interpreter's case)."""
    return math.gcd(m, LANES)


def _offset_counts(rows, cols, m: int):
    """Entries per offset ``cols - rows``, indexed by offset + M - 1: one
    linear pass, where a sort of the COO would take a second at 112³."""
    off = np.asarray(cols, np.int64) - np.asarray(rows, np.int64)
    return off, np.bincount(off + (m - 1), minlength=2 * m - 1)


def offsets_of(rows: np.ndarray, cols: np.ndarray, m: int) -> np.ndarray:
    """The distinct offsets ``cols - rows`` of an M×M COO, ascending."""
    _, counts = _offset_counts(rows, cols, m)
    return np.flatnonzero(counts) - (m - 1)


@dataclasses.dataclass
class Diagonals:
    """An M×M matrix stored by diagonals.

    data:    (n_diag, M/L, L) row-aligned diagonal values.
    offsets: the diagonals' offsets, ascending (static: the kernel unrolls
             one term per diagonal).
    """

    data: jax.Array
    offsets: tuple[int, ...]

    @property
    def m(self) -> int:
        return self.data.shape[1] * self.data.shape[2]

    @staticmethod
    def from_coo(rows: np.ndarray, cols: np.ndarray, vals: np.ndarray,
                 m: int, dtype=np.float64) -> "Diagonals":
        rows = np.asarray(rows, np.int64)
        off, counts = _offset_counts(rows, cols, m)
        present = np.flatnonzero(counts)
        slot = np.zeros(counts.size, np.int64)
        slot[present] = np.arange(present.size)
        offsets = present - (m - 1)
        # one slot per (diagonal, row); duplicates in the COO add up
        data = np.bincount(slot[off + (m - 1)] * m + rows, weights=vals,
                           minlength=offsets.size * m)
        lanes = dia_lanes(m)
        return Diagonals(
            jnp.asarray(data.astype(dtype).reshape(offsets.size, m // lanes,
                                                   lanes)),
            tuple(int(d) for d in offsets))

    def to_dense(self) -> np.ndarray:
        m = self.m
        data = np.asarray(self.data).reshape(len(self.offsets), m)
        out = np.zeros((m, m), data.dtype)
        i = np.arange(m)
        for k, d in enumerate(self.offsets):
            ok = (i + d >= 0) & (i + d < m)
            out[i[ok], i[ok] + d] = data[k, ok]
        return out
