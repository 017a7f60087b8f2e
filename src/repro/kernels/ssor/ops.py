"""Dispatch wrapper for the SSOR apply."""
from __future__ import annotations


from repro.kernels import resolve_backend
from repro.kernels.ssor.ref import ssor_apply_ref
from repro.kernels.ssor.ssor import ssor_apply


def ssor_precond_apply(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv,
                       mid_blocks, r, *, backend: str = "auto",
                       lo_wf=None, up_wf=None):
    backend = resolve_backend(backend, r.dtype)
    if backend == "jnp":
        return ssor_apply_ref(lo_idx, lo_n, lo_data, up_idx, up_n, up_data,
                              dinv, mid_blocks, r, lo_wf=lo_wf, up_wf=up_wf)
    return ssor_apply(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv,
                      mid_blocks, r,
                      interpret=(backend == "interpret"),
                      lo_wf=lo_wf, up_wf=up_wf)
