"""SSOR (symmetric block Gauss-Seidel) preconditioner apply — Pallas path.

  z = ω(2−ω) (D + ωU)^{-1} D (D + ωL)^{-1} r
    = M⁻¹ r  with  M = (1/(ω(2−ω))) (D + ωL) D⁻¹ (D + ωU),

the standard SSOR preconditioner (SPD for SPD A and ω ∈ (0, 2); ω = 1 is
symmetric block Gauss-Seidel). Three passes, all kernelized:

  1. forward blocked substitution   (D + ωL) y = r     (kernels/trisweep)
  2. block-diagonal matvec          w = ω(2−ω) D y     (kernels/block_jacobi)
  3. backward blocked substitution  (D + ωU) z = w     (kernels/trisweep)

The caller pre-scales: ``lo_data``/``up_data`` hold ωL / ωU blocks, ``dinv``
holds D⁻¹ blocks, ``mid_blocks`` holds ω(2−ω) D blocks — all static data
(rebuilt from the COO in safe storage after a failure).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.block_jacobi.block_jacobi import block_jacobi_apply
from repro.kernels.trisweep.trisweep import block_sweep, wavefront_sweep


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssor_apply(lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv,
               mid_blocks, r, *, interpret: bool = False,
               lo_wf=None, up_wf=None):
    """``lo_wf``/``up_wf``: optional level-major ``trisweep.ops.Wavefront``
    bundles — when present the substitutions run one grid step per
    elimination-DAG level instead of per block row (bit-identical values)."""
    if lo_wf is not None:
        y = wavefront_sweep(lo_wf.rows, lo_wf.n, lo_wf.idx, lo_wf.data,
                            lo_wf.dinv, r, interpret=interpret)
    else:
        y = block_sweep(lo_idx, lo_n, lo_data, dinv, r, reverse=False,
                        interpret=interpret)
    w = block_jacobi_apply(mid_blocks, y, interpret=interpret)
    if up_wf is not None:
        return wavefront_sweep(up_wf.rows, up_wf.n, up_wf.idx, up_wf.data,
                               up_wf.dinv, w, interpret=interpret)
    return block_sweep(up_idx, up_n, up_data, dinv, w, reverse=True,
                       interpret=interpret)
