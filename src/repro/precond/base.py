"""Preconditioner protocol + registry — the pluggable subsystem the paper's
conclusion calls for ("these differences can be alleviated by the
implementation of more appropriate preconditioners").

A ``Preconditioner`` owns three things:

  * the hot-loop apply z = P r, per SolverOps backend ("jnp" | "pallas" |
    "interpret") with the repo's bit-identity contract between them;
  * the *recovery-aware* local operators for exact state reconstruction
    (paper Alg. 2 lines 5-6): ``local_ops(mask, f_rows)`` returns
    (offdiag_apply, pff_solve) where

        offdiag_apply(r_surv) = P_{f, I\\f} r_{I\\f}        (line 5)
        pff_solve(v)  solves  P_ff r_f = v                  (line 6)

    For preconditioners with genuine off-diagonal coupling (SSOR, IC(0),
    Chebyshev) the generic path realizes both matrix-free: linearity gives
    P_{f,I\\f} r_{I\\f} = (P r̃)_f with r̃ zeroed on I_f, and P_ff — an SPD
    principal submatrix of P — is solved by inner CG on u ↦ (P ũ)_f, each
    operator application running the preconditioner's real kernels
    (triangular sweeps for SSOR/IC(0), the polynomial recurrence for
    Chebyshev). Block-Jacobi overrides both with its exact closed forms
    (offdiag ≡ 0, P_ff⁻¹ = the raw diagonal blocks) — the seed's Alg. 2
    shortcut, bit-preserved.
  * ``static_state()`` — the serializable static data (host numpy) that a
    replacement node retrieves from safe storage to rebuild the operator
    after a failure (Alg. 2 line 1).

Implementations self-register via ``@register(name)``; ``build(name, ...)``
is the single constructor entry point used by ``sparse.matrices
.build_problem(..., precond=name)``.
"""
from __future__ import annotations

import abc
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

_REGISTRY: dict[str, type] = {}


def tripart_matvec(idx, data, v, b: int):
    """y = T v for a zero-padded ELL triangular strip (jnp, vectorized).

    Invalid slots carry zero blocks (``blocktri._ell_pack``), so no mask is
    needed — this is a *matvec* through the strip, the cheap building block
    of the truncated-operator inner preconditioners (no substitution)."""
    import jax.numpy as jnp

    vb = v.reshape(-1, b)[idx]                       # (nbr, kmax, b)
    return jnp.einsum("nkij,nkj->ni", data, vb).reshape(-1)


def register(name: str):
    """Class decorator: register a Preconditioner under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls
        return cls

    return deco


def available() -> list[str]:
    return sorted(_REGISTRY)


def build(name: str, **ctx) -> "Preconditioner":
    """Build a registered preconditioner from problem context (COO, Block-ELL
    matrix, block size, dtype, precomputed diagonal blocks, plus
    per-preconditioner options)."""
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown preconditioner {name!r}; available: {available()}")
    return _REGISTRY[name].build(**ctx)


class Preconditioner(abc.ABC):
    """Base class: backend-cached applies + generic recovery operators."""

    name: str = "?"
    m: int
    block: int

    # ------------------------------------------------------------------ #
    # hot-loop apply
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def _make_apply(self, backend: str) -> Callable:
        """Backend-specific closure r -> z = P r."""

    def make_apply(self, backend: str = "jnp") -> Callable:
        """Cached per backend: the jitted chunk runners key their compiled
        programs on the SolverOps bundle's ``bind``, which holds this
        closure, so the same object must come back on every call. "auto"
        resolves here, before
        the cache and the subclasses' routing decisions, so "auto" and its
        resolution share one cache entry and the per-backend gates (e.g.
        the wavefront-vs-sequential sweep routing) see a concrete name."""
        if backend == "auto":
            import jax
            backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
        cache = getattr(self, "_apply_cache", None)
        if cache is None:
            cache = {}
            self._apply_cache = cache
        if backend not in cache:
            cache[backend] = self._make_apply(backend)
        return cache[backend]

    def apply(self, r, backend: str = "jnp"):
        return self.make_apply(backend)(r)

    # ------------------------------------------------------------------ #
    # serializable static data (safe storage)
    # ------------------------------------------------------------------ #
    @abc.abstractmethod
    def static_state(self) -> dict:
        """Host-side dict of numpy arrays / plain scalars — everything a
        replacement node needs (beyond the problem's COO) to rebuild the
        operator. Round-trips through ``np.savez``."""

    # ------------------------------------------------------------------ #
    # recovery: Alg. 2 lines 5-6
    # ------------------------------------------------------------------ #
    def local_ops(self, mask: np.ndarray, f_rows: np.ndarray,
                  pff_precond: bool = True
                  ) -> tuple[Optional[Callable], Callable]:
        """(offdiag_apply, pff_solve) for a failed row set.

        Generic matrix-free path (any linear SPD preconditioner):
        ``offdiag_apply(r_surv)`` masks the failed entries and applies the
        full operator; ``pff_solve(v[, rtol, max_iters])`` runs CG on the
        restricted operator u ↦ (P ũ)_f — callers (``esr.reconstruct``)
        thread their ``inner_rtol``/``inner_max_iters`` through, defaulting
        to the paper's line-8 inner-solve tolerance. ``offdiag_apply`` may
        be None, meaning P_{f,I\\f} ≡ 0 exactly (block-Jacobi) so line 5
        degenerates to v = z_f.

        ``pff_precond=True`` (default) preconditions that inner CG with the
        SPD approximation of P_ff⁻¹ the subclass supplies via
        ``_pff_inner_precond`` — for SSOR/IC(0) the failed-slab-truncated
        operator M_ff (cheap triangular *matvecs*, no solves), which makes
        the P_ff solve the dominant recovery cost only by a small constant
        instead of by its condition number (the cost Pachajoa et al.,
        arXiv:1907.13077, identify as dominating reconstruction). The
        closure records ``pff_solve.stats = {"iters", "rel"}`` after each
        run so the recovery report can account for the inner solve.
        """
        from repro.core.pcg import run_pcg

        mask_d = jnp.asarray(mask)
        fr = jnp.asarray(np.asarray(f_rows))
        apply_full = self.make_apply("jnp")
        zeros = jnp.zeros((self.m,), self.dtype)

        def offdiag_apply(r_surv):
            return apply_full(jnp.where(mask_d, 0.0, r_surv))[fr]

        def pff_op(u):
            return apply_full(zeros.at[fr].set(u))[fr]

        inner = self._pff_inner_precond(mask, f_rows) if pff_precond \
            else None
        if inner is None:
            inner = lambda v: v

        def pff_solve(v, rtol: float = 1e-14, max_iters: int = 20_000):
            state, rel = run_pcg(pff_op, inner, v, rtol=rtol,
                                 max_iters=max_iters)
            pff_solve.stats = {"iters": int(state.j), "rel": float(rel)}
            return state.x

        pff_solve.stats = None
        return offdiag_apply, pff_solve

    def _pff_inner_precond(self, mask: np.ndarray, f_rows: np.ndarray
                           ) -> Optional[Callable]:
        """SPD approximation of P_ff⁻¹ preconditioning the line-6 inner CG
        (None = identity). Subclasses with genuine off-diagonal coupling
        override with their failed-slab-truncated operator."""
        return None

    @property
    def dtype(self):
        return self._dtype
