"""Pallas TPU kernels and their pure-jnp references.

Shared conventions for every kernel that has to compile for the chip:

* ``i32`` wraps ``index_map`` results. Under ``jax_enable_x64`` (which the
  entry points and the tests turn on) a Python ``0`` in an index map becomes
  an int64 constant, and Mosaic refuses the kernel ("failed to legalize
  operation 'func.return'"). Grid indices are int32 already; the cast only
  pins the literals.
* ``resolve_backend`` is the one place "auto" is decided, and refuses
  float64 for the Pallas kernels on a TPU.
* ``not_on_chip`` is what a kernel that Mosaic still refuses raises when it
  is asked to run compiled rather than interpreted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def i32(*ix):
    """Index-map result tuple with every entry as int32."""
    return tuple(jnp.asarray(i, jnp.int32) for i in ix)


def resolve_backend(backend: str, dtype) -> str:
    """The kernel backend a call runs on: "auto" is the Pallas kernels on a
    TPU and the jnp references elsewhere. Mosaic compiles 32-bit kernels
    only (XLA's 64-bit rewriter cannot split a Mosaic call), so f64 data
    on a TPU is refused here, before anything is traced, rather than
    failing deep in the compiler or running somewhere else."""
    on_tpu = jax.default_backend() == "tpu"
    if backend == "auto":
        backend = "pallas" if on_tpu else "jnp"
    if backend == "pallas" and on_tpu and np.dtype(dtype) == np.float64:
        raise ValueError(
            "the Pallas kernels cannot run float64 data on a TPU: build the "
            "problem with dtype=np.float32, or pick backend='jnp'")
    return backend


def not_on_chip(name: str, why: str):
    raise NotImplementedError(
        f"the {name} Pallas kernel does not compile for the TPU ({why}); "
        f"use backend='jnp' (or 'interpret' to validate it on a CPU)")
