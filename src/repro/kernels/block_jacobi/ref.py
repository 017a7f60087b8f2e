"""Pure-jnp mirror of the block-Jacobi apply kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_pcg.fused_pcg import (block_apply, jacobi_diagonals,
                                               lanes_for)


@jax.jit
def block_jacobi_apply_ref(pinv_blocks, r):
    """The kernel's arithmetic on the whole (M/L, L) view at once. It is
    elementwise, so the result is bit-identical to the kernel's at any
    row blocking. Jitted, so that XLA contracts its multiply-adds as it
    does the kernel's."""
    m = r.shape[0]
    b = pinv_blocks.shape[-1]
    lanes = lanes_for(m, b)
    return block_apply(jacobi_diagonals(pinv_blocks, lanes),
                       r.reshape(-1, lanes), b,
                       lambda v, s: jnp.roll(v, s, axis=1)).reshape(m)
