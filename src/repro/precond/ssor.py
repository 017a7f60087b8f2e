"""SSOR / symmetric block Gauss-Seidel preconditioner.

  M = (1/(ω(2−ω))) (D + ωL) D⁻¹ (D + ωU),   z = M⁻¹ r

with D = blockdiag(A_bb), L/U = strictly-block-lower/-upper parts of A at
the preconditioner block granularity, ω ∈ (0, 2) (ω = 1 → symmetric block
Gauss-Seidel). SPD for SPD A. Unlike block-Jacobi this couples across node
boundaries — P = M⁻¹ has genuine off-diagonal structure, so Alg. 2
reconstruction runs the generic recovery-aware path (masked full apply for
line 5, inner CG over the sweeps for line 6) inherited from the base class.

Static data: the ω-scaled triangular block strips, D blocks and their
Cholesky inverses — all rebuildable from the COO in safe storage.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.precond.base import Preconditioner, register
from repro.precond.blocktri import TriPart, block_split, wavefront_pair
from repro.precond.jacobi import invert_blocks


@register("ssor")
class SSOR(Preconditioner):
    def __init__(self, lo_idx, lo_n, lo_data, up_idx, up_n, up_data, dinv,
                 mid_blocks, block: int, m: int, dtype, omega: float,
                 sweep_mode: str = "auto"):
        # level schedules are derived host-side from the triangular
        # structure before the device upload; "auto" keeps the sequential
        # kernels on chain-structured DAGs (see blocktri.wavefront_favorable)
        self.sweep_mode = sweep_mode
        self.lo_wf, self.up_wf = wavefront_pair(
            TriPart(np.asarray(lo_idx), np.asarray(lo_n),
                    np.asarray(lo_data)),
            TriPart(np.asarray(up_idx), np.asarray(up_n),
                    np.asarray(up_data)),
            np.asarray(dinv), np.asarray(dinv), m // block, sweep_mode)
        self.lo_idx = jnp.asarray(lo_idx)
        self.lo_n = jnp.asarray(lo_n)
        self.lo_data = jnp.asarray(lo_data)
        self.up_idx = jnp.asarray(up_idx)
        self.up_n = jnp.asarray(up_n)
        self.up_data = jnp.asarray(up_data)
        self.dinv = jnp.asarray(dinv)
        self.mid_blocks = jnp.asarray(mid_blocks)
        self.block = block
        self.m = m
        self._dtype = dtype
        self.omega = omega

    @classmethod
    def build(cls, *, coo, m, block, dtype, omega: float = 1.0,
              pinv_blocks=None, sweep_mode: str = "auto", **_):
        if not 0.0 < omega < 2.0:
            raise ValueError(f"SSOR needs omega in (0, 2), got {omega}")
        rows, cols, vals = coo
        diag, lower, upper = block_split(rows, cols, vals, m, block, dtype)
        dinv = (np.asarray(pinv_blocks) if pinv_blocks is not None
                else invert_blocks(diag))
        return cls(lower.idx, lower.n, omega * lower.data,
                   upper.idx, upper.n, omega * upper.data,
                   dinv, (omega * (2.0 - omega)) * diag,
                   block, m, dtype, omega, sweep_mode)

    def _make_apply(self, backend: str):
        from repro.kernels.ssor.ops import ssor_precond_apply

        args = (self.lo_idx, self.lo_n, self.lo_data, self.up_idx, self.up_n,
                self.up_data, self.dinv, self.mid_blocks)
        # the wavefront shortens the sequential *kernel grid* (one step per
        # DAG level); the jnp reference runs its rows serially either way,
        # so it keeps the unpadded sequential sweep unless explicitly forced
        # — bit-identity between the routes is a tested invariant, so mixed
        # routing cannot fork the backends' trajectories
        wf = backend != "jnp" or self.sweep_mode == "wavefront"
        lo_wf = self.lo_wf if wf else None
        up_wf = self.up_wf if wf else None
        return lambda r: ssor_precond_apply(*args, r, backend=backend,
                                            lo_wf=lo_wf, up_wf=up_wf)

    def _pff_inner_precond(self, mask, f_rows):
        """Failed-slab-truncated SSOR matrix: B = M_ff with
        M = (1/(ω(2−ω))) (D + ωL) D⁻¹ (D + ωU).

        P_ff = (M⁻¹)_ff, whose inverse M_ff approximates up to the slab's
        off-diagonal coupling, and M_ff is an SPD principal submatrix of M
        — so CG on P_ff preconditioned with B converges in a handful of
        iterations instead of O(√cond(P_ff)). Each B apply is two
        triangular *matvecs* plus three block-diagonal einsums (no
        substitution sweeps)."""
        from repro.precond.base import tripart_matvec

        fr = jnp.asarray(np.asarray(f_rows))
        zeros = jnp.zeros((self.m,), self.dtype)
        b = self.block
        inv_s = 1.0 / (self.omega * (2.0 - self.omega))
        lo_idx, lo_data = self.lo_idx, self.lo_data
        up_idx, up_data = self.up_idx, self.up_data
        mid, dinv = self.mid_blocks, self.dinv

        def dmat(v):                                  # D v (mid = ω(2−ω) D)
            return inv_s * jnp.einsum("nij,nj->ni", mid,
                                      v.reshape(-1, b)).reshape(-1)

        def inner(u):
            v = zeros.at[fr].set(u)
            t = dmat(v) + tripart_matvec(up_idx, up_data, v, b)
            s = jnp.einsum("nij,nj->ni", dinv,
                           t.reshape(-1, b)).reshape(-1)
            mv = inv_s * (dmat(s) + tripart_matvec(lo_idx, lo_data, s, b))
            return mv[fr]

        return inner

    def static_state(self) -> dict:
        return {"lo_idx": np.asarray(self.lo_idx),
                "lo_n": np.asarray(self.lo_n),
                "lo_data": np.asarray(self.lo_data),
                "up_idx": np.asarray(self.up_idx),
                "up_n": np.asarray(self.up_n),
                "up_data": np.asarray(self.up_data),
                "dinv": np.asarray(self.dinv),
                "mid_blocks": np.asarray(self.mid_blocks),
                "block": self.block, "omega": self.omega,
                "sweep_mode": self.sweep_mode}

    @classmethod
    def from_static(cls, state, *, m: int, dtype, **_):
        return cls(state["lo_idx"], state["lo_n"], state["lo_data"],
                   state["up_idx"], state["up_n"], state["up_data"],
                   state["dinv"], state["mid_blocks"], int(state["block"]),
                   m, dtype, float(state["omega"]),
                   str(state.get("sweep_mode", "auto")))
