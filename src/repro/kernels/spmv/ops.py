"""Jit'd dispatch wrappers: Pallas kernels on TPU, interpret-mode kernels for
validation, jnp oracles as the default CPU path."""
from __future__ import annotations

import jax

from repro.kernels import resolve_backend
from repro.kernels.spmv.ref import spmv_ref
from repro.kernels.spmv.spmv import spmv as spmv_pallas
from repro.sparse.blockell import BlockEll


def blockell_matvec(a: BlockEll, x: jax.Array, *, backend: str = "auto"):
    """backend: "auto" (pallas on TPU else jnp), "pallas", "interpret",
    "jnp". (The fused SpMV+dot variant is routed by repro.core.ops, which
    owns the solver-side backend dispatch.)"""
    backend = resolve_backend(backend, x.dtype)
    if backend == "jnp":
        return spmv_ref(a.data, a.idx, x)
    return spmv_pallas(a.data, a.idx, x,
                       interpret=(backend == "interpret"))
