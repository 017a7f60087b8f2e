"""Dispatch wrapper for the fused PCG update."""
from __future__ import annotations

from repro.kernels import resolve_backend
from repro.kernels.fused_pcg.fused_pcg import (fused_pcg_update,
                                               jacobi_diagonals, lanes_for,
                                               update_rows)
from repro.kernels.fused_pcg.ref import fused_pcg_update_ref


def pcg_update(alpha, x, r, p, q, pinv_blocks, *, backend: str = "auto",
               rows: int | None = None):
    backend = resolve_backend(backend, x.dtype)
    if backend == "jnp":
        return fused_pcg_update_ref(alpha, x, r, p, q, pinv_blocks)
    b = pinv_blocks.shape[1]
    rows = rows or update_rows(x.shape[0], b)
    dia = jacobi_diagonals(pinv_blocks, lanes_for(rows, b))
    return fused_pcg_update(alpha, x, r, p, q, dia, rows=rows,
                            interpret=(backend == "interpret"))
