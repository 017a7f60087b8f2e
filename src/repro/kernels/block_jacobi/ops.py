"""Dispatch wrapper for block-Jacobi apply."""
from __future__ import annotations


from repro.kernels import resolve_backend
from repro.kernels.block_jacobi.block_jacobi import block_jacobi_apply
from repro.kernels.block_jacobi.ref import block_jacobi_apply_ref


def precond_apply(pinv_blocks, r, *, backend: str = "auto",
                  rows: int | None = None):
    backend = resolve_backend(backend, r.dtype)
    if backend == "jnp":
        return block_jacobi_apply_ref(pinv_blocks, r)
    return block_jacobi_apply(pinv_blocks, r, rows=rows,
                              interpret=(backend == "interpret"))
