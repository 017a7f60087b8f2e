"""Roofline attribution: price each dispatched solver kernel's lowered HLO
once at build time with the seed analyzer (``roofline/hlo_analysis``).

ROADMAP's "real-accelerator perf campaign" item wants every kernel gated
against a roofline target computed from the HLO, reported in BENCH_*.json.
This module is the bridge: ``solver_rooflines`` lowers the SolverOps bundle's
kernels (SpMV, fused SpMV+dot, preconditioner apply, fused update, and the
whole PCG iteration) against shape-only abstract inputs, runs the while-aware
cost analyzer over the compiled text, and returns FLOP / HBM-byte /
collective-byte counts plus the FLOP/byte arithmetic intensity per kernel.
``benchmarks/run.py`` embeds the result in its BENCH_*.json (CI fails if
absent).

Costs are analyzer-model numbers over the *post-optimization* HLO of the
current backend — a per-program traffic floor for relative comparison, not a
measured hardware counter (same caveat as ``roofline/report.py``).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.roofline.hlo_analysis import analyze


def kernel_roofline(fn, *args, label: str = "kernel") -> dict:
    """Lower+compile ``fn`` on the given abstract args and price the HLO.
    Returns a JSON-safe dict; a kernel that cannot lower in this context
    (e.g. a mesh-bound shard_map closure outside its mesh) degrades to an
    ``error`` entry instead of failing the solve."""
    try:
        text = jax.jit(fn).lower(*args).compile().as_text()
        costs = analyze(text)
        out = dict(kernel=label, flops=float(costs.flops),
                   hbm_bytes=float(costs.hbm_bytes),
                   collective_bytes=float(costs.collective_bytes),
                   flop_per_byte=float(costs.flops
                                       / max(costs.hbm_bytes, 1.0)))
        if costs.while_trips:
            out["while_trips"] = {k: int(v)
                                  for k, v in costs.while_trips.items()}
        return out
    except Exception as e:                  # noqa: BLE001 - observability
        return dict(kernel=label, error=f"{type(e).__name__}: {e}")


def solver_rooflines(ops, b) -> dict[str, dict]:
    """FLOP/byte attribution for the kernels a resilient solve dispatches
    through the SolverOps bundle, keyed by kernel name. ``b`` supplies the
    vector shape/dtype (no data is read — lowering is shape-only)."""
    from repro.core.pcg import PCGState, pcg_iterate_ops

    vec = jax.ShapeDtypeStruct(np.shape(b), b.dtype)
    scalar = jax.ShapeDtypeStruct((), b.dtype)
    state = PCGState(x=vec, r=vec, z=vec, p=vec, rz=scalar, beta=scalar,
                     j=jax.ShapeDtypeStruct((), np.int32))
    kernels = {
        "spmv": (ops.matvec, (vec,)),
        "spmv_dot": (ops.matvec_dot, (vec,)),
        "precond": (ops.precond, (vec,)),
        "update": (lambda a, x, r, p, q: ops.update(a, x, r, p, q),
                   (scalar, vec, vec, vec, vec)),
        "iteration": (lambda s: pcg_iterate_ops(s, ops), (state,)),
    }
    return {name: kernel_roofline(fn, *args, label=name)
            for name, (fn, args) in kernels.items()}
