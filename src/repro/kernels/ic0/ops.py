"""Dispatch wrapper for the IC(0) apply."""
from __future__ import annotations


from repro.kernels import resolve_backend
from repro.kernels.ic0.ic0 import ic0_apply
from repro.kernels.ic0.ref import ic0_apply_ref


def ic0_precond_apply(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv_f,
                      dinv_b, r, *, backend: str = "auto", lo_wf=None,
                      up_wf=None):
    backend = resolve_backend(backend, r.dtype)
    if backend == "jnp":
        return ic0_apply_ref(lo_idx, lo_n, lo_data, up_idx, up_n, up_data,
                             dinv_f, dinv_b, r, lo_wf=lo_wf, up_wf=up_wf)
    return ic0_apply(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv_f,
                     dinv_b, r, interpret=(backend == "interpret"),
                     lo_wf=lo_wf, up_wf=up_wf)
