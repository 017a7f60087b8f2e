"""Chip compiles without a chip: the main-path Pallas kernels and the ESRP
chunk runner, compiled for a described TPU v5e at the ``chip_smoke.py``
size (poisson3d 128³, 128x128 Block-ELL tiles, 8x8 block-Jacobi, float32)
under this suite's ``jax_enable_x64``. What the chip's compiler refuses —
a block off the (8, 128) tiling, an int64 index map, an SMEM array too
large — fails here at no chip time. Plus the backend resolution a TPU sees:
"pallas" refuses float64, "auto" never picks the jnp references.

The topology is described only inside the module fixture: one xdist worker
loads the TPU compiler, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_pcg.fused_pcg import (LANES, fused_pcg_update,
                                               fused_pcg_update_batched,
                                               update_rows)
from repro.kernels.spmv.spmv import (spmv, spmv_batched, spmv_dot,
                                     spmv_dot_batched)
from repro.sparse.matrices import build_problem

M = 128 ** 3            # chip_smoke.py: poisson3d at 128³
TILE = 128
KMAX = 5                # 7-point stencil, 128-wide tiles: own, ±1, ±128
BLOCK = 8
NODES = 16
B = 4
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(dev):
    def s(shape, dtype=F32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)
    return s


def _kernel_case(name, batch, s):
    data = s((M // TILE, KMAX, TILE, TILE))
    idx = s((M // TILE, KMAX), jnp.int32)
    vec = s((batch, M) if batch else (M,))
    dia = s((2 * BLOCK - 1, M // LANES, LANES))
    rows = update_rows(M, BLOCK)
    if name == "fused_pcg_update":
        fn = fused_pcg_update_batched if batch else fused_pcg_update
        alpha = s((batch,) if batch else ())
        return (lambda a, x, r, p, q, d: fn(a, x, r, p, q, d, rows=rows),
                (alpha, vec, vec, vec, vec, dia))
    fn = {("spmv", False): spmv, ("spmv", True): spmv_batched,
          ("spmv_dot", False): spmv_dot,
          ("spmv_dot", True): spmv_dot_batched}[name, bool(batch)]
    return (lambda d, i, x: fn(d, i, x)), (data, idx, vec)


@pytest.mark.parametrize("batch", [0, B])
@pytest.mark.parametrize("name", ["spmv", "spmv_dot", "fused_pcg_update"])
def test_main_path_kernel_compiles_for_v5e(one_chip, name, batch):
    fn, args = _kernel_case(name, batch, _shapes(one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_block_jacobi_apply_compiles_for_v5e(one_chip):
    """The standalone apply (the preconditioner's own, SSOR's middle
    pass) shares the fused update's lane-diagonal body."""
    from repro.kernels.block_jacobi.block_jacobi import block_jacobi_apply

    s = _shapes(one_chip)
    compiled = jax.jit(block_jacobi_apply).lower(
        s((M // BLOCK, BLOCK, BLOCK)), s((M,))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_esrp_chunk_runner_compiles_f32_with_mosaic_kernels(one_chip):
    """The jitted ESRP chunk runner through the Pallas bundle, built from
    shapes only: the bundle's arrays are arguments of the compiled
    program. No f64 vector may appear in it."""
    import re

    from repro.core import esrp
    from repro.core.ops import make_problem_ops
    from repro.sparse.blockell import BlockEll
    from repro.sparse.matrices import Problem
    from repro.sparse.partition import Partition

    s = _shapes(one_chip)

    def runner(data, idx, nblk, pinv, b):
        problem = Problem(
            a=BlockEll(data, idx, nblk, (M, M), TILE, TILE),
            part=Partition(m=M, n_nodes=NODES, bm=TILE, bn=TILE), b=b,
            pinv_blocks=pinv, diag_blocks=pinv, precond_block=BLOCK,
            coo=None)
        ops = make_problem_ops(problem, "pallas")
        st = esrp.esrp_init(ops.matvec, ops.precond, b, dot=ops.dot)
        return esrp.run_chunk(st, ops, 20, 64, jnp.asarray(1e-6, b.dtype))

    lowered = jax.jit(runner).lower(
        s((M // TILE, KMAX, TILE, TILE)), s((M // TILE, KMAX), jnp.int32),
        s((M // TILE,), jnp.int32), s((M // BLOCK, BLOCK, BLOCK)), s((M,)))
    text = lowered.as_text()
    assert not re.findall(r"tensor<(?:\d+x)+f64>", text)
    assert text.count("tpu_custom_call") >= 2
    lowered.compile()


@pytest.fixture
def on_tpu(monkeypatch):
    """Backend resolution as a TPU process sees it (nothing is compiled)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("backend", ["pallas", "auto"])
def test_pallas_refuses_float64_on_tpu(on_tpu, backend):
    p = build_problem("poisson2d", n_nodes=4, nx=16, dtype=np.float64)
    with pytest.raises(ValueError, match="float64"):
        p.solver_ops(backend)


def test_auto_resolves_to_pallas_on_tpu(on_tpu):
    p = build_problem("poisson2d", n_nodes=4, nx=16, dtype=np.float32)
    assert p.solver_ops("auto").backend == "pallas"
    assert p.solver_ops("auto", batch=2).backend == "pallas"


@pytest.mark.parametrize("kernel", ["ssor", "trisweep", "attention"])
def test_off_main_path_kernels_refuse_to_compile(kernel):
    """Kernels Mosaic still refuses raise before tracing, instead of
    failing deep in the compiler or falling back to a reference."""
    x = jnp.zeros(64, F32)
    with pytest.raises(NotImplementedError, match="does not compile"):
        if kernel == "ssor":
            from repro.kernels.ssor.ssor import ssor_apply
            z8 = jnp.zeros((8, 1), jnp.int32)
            t8 = jnp.zeros((8, 1, 8, 8), F32)
            b8 = jnp.zeros((8, 8, 8), F32)
            ssor_apply(z8, z8[:, 0], t8, z8, z8[:, 0], t8, b8, b8, x)
        elif kernel == "trisweep":
            from repro.kernels.trisweep.trisweep import block_sweep
            block_sweep(jnp.zeros((8, 1), jnp.int32), jnp.zeros(8, jnp.int32),
                        jnp.zeros((8, 1, 8, 8), F32),
                        jnp.zeros((8, 8, 8), F32), x)
        else:
            from repro.kernels.attention.flash import flash_attention
            q = jnp.zeros((1, 16, 8), F32)
            flash_attention(q, q, q)
