"""The plain reference a solve is judged by, and the lower-precision control.

``true_residual`` is ||b - A x|| / ||b|` in float64 on the host, with the
configuration's operator built by ``bench/operators/<operator>.py``: it is
independent of the program's Block-ELL layout and of every kernel.

``solve`` is a plain preconditioned CG (block-Jacobi, blocks inverted here
from the reference operator) in float32 on the device. At ``"highest"`` it
is the reference solve. At a lower precision it is a control: every product
of the operator, the preconditioner and the inner products is formed as a
TPU matmul at that precision forms it, written out so that it reads the
same on any backend: ``"high"`` (bf16_3x: each factor split into a bfloat16
head and tail, the tail-by-tail product dropped, sums in float32) and
``"default"`` (one bfloat16 pass: both factors rounded to bfloat16, sums in
float32). Each rounding to bfloat16 is ``lax.reduce_precision``, which the
compiler keeps: a round trip through a bfloat16 array may be folded away
where excess precision is allowed, as it is on the TPU.
"""
from __future__ import annotations

import functools

import numpy as np

PRECISIONS = ("highest", "high", "default")


def true_residual(a_csr, x, b) -> float:
    b = np.asarray(b, np.float64)
    r = b - a_csr @ np.asarray(x, np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def block_jacobi_inverse(a_csr, block: int) -> np.ndarray:
    """(M/block, block, block) inverses of A's diagonal blocks, float64."""
    m = a_csr.shape[0]
    if m % block:
        raise ValueError(f"M = {m} is not a multiple of the block {block}")
    coo = a_csr.tocoo()
    keep = coo.row // block == coo.col // block
    blocks = np.zeros((m // block, block, block))
    blocks[coo.row[keep] // block, coo.row[keep] % block,
           coo.col[keep] % block] = coo.data[keep]
    return np.linalg.inv(blocks)


def _bf16(v):
    """``v`` (float32) rounded to bfloat16's 8 significant bits."""
    import jax
    import jax.numpy as jnp

    return jax.lax.reduce_precision(jnp.asarray(v, jnp.float32),
                                    exponent_bits=8, mantissa_bits=7)


def _split(v):
    hi = _bf16(v)
    return hi, _bf16(v - hi)


def tree_sum(v, width: int = 128):
    """Sum in float32 by rows of ``width``, then rows of the row sums, and
    so on: the error grows with the log of the length, as in a pairwise
    sum, and not with the length."""
    import jax.numpy as jnp

    while v.size > width:
        pad = -v.size % width
        v = jnp.sum(jnp.pad(v, (0, pad)).reshape(-1, width), axis=1)
    return jnp.sum(v)


def products(precision: str):
    """(mul, dot, bmv): coefficient times vector, inner product and
    block-matrix apply, formed at ``precision``."""
    import jax
    import jax.numpy as jnp

    hp = jax.lax.Precision.HIGHEST
    if precision == "highest":
        return (lambda c, v: c * v,
                lambda a, b: tree_sum(a * b),
                lambda p, r: jnp.einsum("kij,kj->ki", p, r, precision=hp))
    if precision == "default":
        return (lambda c, v: _bf16(c) * _bf16(v),
                lambda a, b: tree_sum(_bf16(a) * _bf16(b)),
                lambda p, r: jnp.einsum("kij,kj->ki", _bf16(p), _bf16(r),
                                        precision=hp))
    if precision != "high":
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")

    def mul(c, v):
        c = jnp.asarray(c, jnp.float32)
        (ch, cl), (vh, vl) = _split(c), _split(v)
        return ch * vh + (ch * vl + cl * vh)

    def dot(a, b):
        return tree_sum(mul(a, b))

    def bmv(p, r):
        (ph, pl), (rh, rl) = _split(p), _split(r)
        f = functools.partial(jnp.einsum, "kij,kj->ki", precision=hp)
        return f(ph, rh) + (f(ph, rl) + f(pl, rh))

    return mul, dot, bmv


@functools.lru_cache(maxsize=None)
def _solver(operator, cfg_items: tuple, precision: str, max_iters: int):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    mul, dot, bmv = products(precision)
    block = int(cfg["precond_block"])

    def precond(pinv, r):
        return bmv(pinv, r.reshape(-1, block)).reshape(-1)

    @jax.jit
    def run(pinv, b, rtol):
        matvec = lambda v: operator.apply(cfg, v, mul)
        thresh = rtol * jnp.sqrt(dot(b, b))
        x = jnp.zeros_like(b)
        z = precond(pinv, b)
        init = (x, b, z, dot(b, z), jnp.asarray(0, jnp.int32))

        def cond(s):
            _, r, _, _, k = s
            return (jnp.sqrt(dot(r, r)) >= thresh) & (k < max_iters)

        def body(s):
            x, r, p, rz, k = s
            q = matvec(p)
            alpha = rz / dot(p, q)
            x = x + alpha * p
            r = r - alpha * q
            z = precond(pinv, r)
            rz_new = dot(r, z)
            return x, r, z + (rz_new / rz) * p, rz_new, k + 1

        x, r, _, _, k = jax.lax.while_loop(cond, body, init)
        return x, k, jnp.sqrt(dot(r, r) / dot(b, b))

    return run


def solve(operator, cfg: dict, pinv, b, rtol: float, precision: str,
          max_iters: int = 20_000):
    """Plain PCG on the device: returns (x, iterations, ||r||/||b||) with
    the recursive residual ``r``. ``pinv`` is ``block_jacobi_inverse`` as a
    float32 device array."""
    import jax.numpy as jnp

    keys = ("nx", "ny", "nz", "eps", "precond_block")
    run = _solver(operator, tuple((k, cfg[k]) for k in keys if k in cfg),
                  precision, max_iters)
    x, k, rel = run(pinv, jnp.asarray(b, jnp.float32),
                    jnp.asarray(rtol, jnp.float32))
    return x, int(k), float(rel)
