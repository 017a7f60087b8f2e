"""Plain reference of the 3D 7-point finite-difference Laplacian (Dirichlet),
written from its formula: unknown i = x + nx*(y + ny*z), diagonal 2 + 4 eps,
-1 to the x neighbours and -eps to the y and z neighbours. It shares no code
with the program."""
from __future__ import annotations

import numpy as np

# A equals its transpose: each coupling is stored at (i, j) and at (j, i)
# with the same value
SYMMETRIC = True


def dims(cfg: dict) -> tuple[int, int, int]:
    nx = int(cfg["nx"])
    return nx, int(cfg.get("ny", nx)), int(cfg.get("nz", nx))


def size(cfg: dict) -> int:
    nx, ny, nz = dims(cfg)
    return nx * ny * nz


def _pairs(cfg: dict) -> int:
    """Neighbour pairs of the grid, in x, y and z."""
    nx, ny, nz = dims(cfg)
    return (nx - 1) * ny * nz + nx * (ny - 1) * nz + nx * ny * (nz - 1)


def nnz(cfg: dict) -> int:
    """Stored nonzeros: every point, plus one coupling per neighbour pair in
    each direction, counted from both ends."""
    return size(cfg) + 2 * _pairs(cfg)


def nnz_upper(cfg: dict) -> int:
    """Stored nonzeros on or above the diagonal: every point, plus each
    neighbour pair once."""
    return size(cfg) + _pairs(cfg)


def csr(cfg: dict):
    """The operator as a float64 scipy CSR matrix: a Kronecker sum of 1D
    second differences."""
    import scipy.sparse as sp

    nx, ny, nz = dims(cfg)
    eps = float(cfg.get("eps", 1.0))

    def second_diff(n):
        return sp.diags([-np.ones(n - 1), 2 * np.ones(n), -np.ones(n - 1)],
                        [-1, 0, 1], format="csr")

    def eye(n):
        return sp.identity(n, format="csr")

    a = (sp.kron(eye(nz), sp.kron(eye(ny), second_diff(nx)))
         + eps * sp.kron(eye(nz), sp.kron(second_diff(ny), eye(nx)))
         + eps * sp.kron(second_diff(nz), sp.kron(eye(ny), eye(nx))))
    return sp.csr_matrix(a, dtype=np.float64)


def apply(cfg: dict, x, mul):
    """y = A x on the device for a flat x, as a stencil: ``mul(c, v)`` forms
    each coefficient's product, so that the caller sets its precision."""
    import jax.numpy as jnp

    nx, ny, nz = dims(cfg)
    eps = float(cfg.get("eps", 1.0))
    g = x.reshape(nz, ny, nx)
    y = mul(2.0 + 4.0 * eps, g)
    for axis, c in ((2, -1.0), (1, -eps), (0, -eps)):
        n = g.shape[axis]
        lo = [slice(None)] * 3
        hi = [slice(None)] * 3
        lo[axis], hi[axis] = slice(0, n - 1), slice(1, n)
        pad_lo = [(0, 0)] * 3
        pad_hi = [(0, 0)] * 3
        pad_lo[axis], pad_hi[axis] = (1, 0), (0, 1)
        # neighbour at -1 along the axis, and at +1
        y = y + mul(c, jnp.pad(g[tuple(lo)], pad_lo))
        y = y + mul(c, jnp.pad(g[tuple(hi)], pad_hi))
    return y.reshape(-1)


def program_args(cfg: dict) -> dict:
    """The operator's sizes as the program's ``build_problem`` takes them."""
    nx, ny, nz = dims(cfg)
    return {"nx": nx, "ny": ny, "nz": nz, "eps": float(cfg.get("eps", 1.0))}


def symmetries(cfg: dict) -> int:
    """Transforms of a right-hand side under which the solve does the same
    work: a sign, and a reflection of the grid along x, y and z. Reflecting
    x keeps the block-Jacobi blocks (runs of ``precond_block`` along x)
    whole only where that block divides nx, so x is left out otherwise."""
    nx, _, _ = dims(cfg)
    return 16 if nx % int(cfg.get("precond_block", 1)) == 0 else 8


def transform(cfg: dict, v, t: int):
    """The ``t``-th transform of a flat vector: bit 0 flips the sign, bits
    1-3 reflect z, y and x."""
    import jax.numpy as jnp

    nx, ny, nz = dims(cfg)
    g = v.reshape(nz, ny, nx)
    axes = tuple(a for a, bit in ((0, 2), (1, 4), (2, 8)) if t & bit)
    if axes:
        g = jnp.flip(g, axes)
    return (-g if t & 1 else g).reshape(-1)
