"""Dispatch wrapper for the Chebyshev apply."""
from __future__ import annotations


from repro.kernels import resolve_backend
from repro.kernels.chebyshev.chebyshev import chebyshev_apply
from repro.kernels.chebyshev.ref import chebyshev_apply_ref


def chebyshev_precond_apply(data, idx, r, *, lo: float, hi: float,
                            degree: int, backend: str = "auto"):
    backend = resolve_backend(backend, r.dtype)
    if backend == "jnp":
        return chebyshev_apply_ref(data, idx, r, lo=lo, hi=hi, degree=degree)
    return chebyshev_apply(data, idx, r, lo=lo, hi=hi, degree=degree,
                           interpret=(backend == "interpret"))
