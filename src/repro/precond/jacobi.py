"""Block-Jacobi preconditioner — P = blockdiag(A_bb)⁻¹ (paper §5: uniform
blocks, max size 10, never straddling node boundaries).

Migrated here from ``sparse/matrices.py``; the block extraction and the
Cholesky-based batched inverse are host-side static data. The recovery
operators are the exact closed forms the seed hard-wired into Alg. 2:
P has zero off-diagonal (line 5: v = z_f), and P_ff⁻¹ is the raw diagonal
blocks (line 6: a block matvec) — overriding the generic matrix-free path so
the default configuration stays bit-identical to the pre-subsystem code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.precond.base import Preconditioner, register


def block_jacobi_blocks(rows, cols, vals, m: int, b: int,
                        dtype=np.float64) -> np.ndarray:
    """Extract the (m/b, b, b) diagonal blocks of A (host-side, static)."""
    if m % b:
        raise ValueError(f"M={m} not divisible by precond block {b}")
    blk_r, blk_c = rows // b, cols // b
    on = blk_r == blk_c
    out = np.zeros((m // b, b, b), dtype)
    np.add.at(out, (blk_r[on], rows[on] % b, cols[on] % b), vals[on])
    return out


def invert_blocks(blocks: np.ndarray) -> np.ndarray:
    """P = blockdiag(A_bb)^{-1}; batched Cholesky-based inverse.

    A_bb⁻¹ = L⁻ᵀ L⁻¹ from A_bb = L Lᵀ: better conditioned than the general
    LU inverse, exactly symmetric by construction, and ``np.linalg.cholesky``
    raising on a non-positive-definite block doubles as an SPD validation of
    the problem setup."""
    try:
        chol = np.linalg.cholesky(blocks)
    except np.linalg.LinAlgError as e:
        raise np.linalg.LinAlgError(
            "block-Jacobi blocks are not SPD — the problem matrix is not "
            f"symmetric positive definite ({e})") from e
    eye = np.broadcast_to(np.eye(blocks.shape[-1], dtype=blocks.dtype),
                          blocks.shape)
    linv = np.linalg.solve(chol, eye)            # L⁻¹, batched
    return np.swapaxes(linv, -1, -2) @ linv


@register("jacobi")
class BlockJacobi(Preconditioner):
    def __init__(self, diag_blocks, pinv_blocks, block: int, m: int, dtype):
        self.diag_blocks = jnp.asarray(diag_blocks)
        self.pinv_blocks = jnp.asarray(pinv_blocks)
        self.block = block
        self.m = m
        self._dtype = dtype

    @classmethod
    def build(cls, *, coo, m, block, dtype, diag_blocks=None,
              pinv_blocks=None, **_):
        if diag_blocks is None:
            rows, cols, vals = coo
            diag_blocks = block_jacobi_blocks(rows, cols, vals, m, block,
                                              dtype)
        if pinv_blocks is None:
            pinv_blocks = invert_blocks(np.asarray(diag_blocks))
        return cls(diag_blocks, pinv_blocks, block, m, dtype)

    def _make_apply(self, backend: str):
        from repro.kernels.block_jacobi.block_jacobi import block_jacobi_apply
        from repro.kernels.block_jacobi.ref import block_jacobi_apply_ref

        pinv = self.pinv_blocks
        if backend == "jnp":
            return lambda r: block_jacobi_apply_ref(pinv, r)
        interp = backend == "interpret"
        return lambda r: block_jacobi_apply(pinv, r, interpret=interp)

    def static_state(self) -> dict:
        return {"diag_blocks": np.asarray(self.diag_blocks),
                "pinv_blocks": np.asarray(self.pinv_blocks),
                "block": self.block}

    @classmethod
    def from_static(cls, state, *, m: int, dtype, **_):
        return cls(state["diag_blocks"], state["pinv_blocks"],
                   int(state["block"]), m, dtype)

    def local_ops(self, mask, f_rows, **_):
        """Exact closed forms: P offdiag ≡ 0 (None), P_ff⁻¹ = A_bb blocks."""
        b = self.block
        blk_ids = np.unique(np.asarray(f_rows) // b)
        diag_f = self.diag_blocks[jnp.asarray(blk_ids)]

        def pff_solve(v, rtol=None, max_iters=None):
            # exact direct solve — the tolerance knobs don't apply
            return jnp.einsum("nij,nj->ni", diag_f,
                              v.reshape(-1, b)).reshape(-1)

        return None, pff_solve
