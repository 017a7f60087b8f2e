"""The operator stored by diagonals: the format, the choice between it and
Block-ELL, and the diagonal SpMV / SpMV+dot kernel (interpret mode) against
the dense operator, its jnp mirror and its own B = 1 run, in f64."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.driver import solve_resilient
from repro.core.ops import problem_spmv
from repro.kernels.spmv.dia import (DiaLayout, dia_layout, halo_rows,
                                    spmv_dia, spmv_dia_batched, spmv_dia_dot,
                                    spmv_dia_dot_batched)
from repro.kernels.spmv.ref import (spmv_dia_dot_ref,
                                    spmv_dia_dot_ref_batched, spmv_dia_ref)
from repro.obs import Tracer, span_tree, walk_spans
from repro.sparse.blockell import BlockEll
from repro.sparse.dia import Diagonals, offsets_of
from repro.sparse.matrices import Problem, build_problem, poisson3d
from repro.sparse.partition import Partition

# (kind, kwargs, VMEM budget of the layout): None keeps the default (two
# grid steps at these sizes); a small budget splits the vector into more,
# whose halos then carry the long offsets
CASES = {
    "poisson2d": ("poisson2d", dict(nx=16, ny=16), None),
    # nx = 16 on 128 lanes: ±16 spills into the next lane row, ±256 is 2 rows
    "poisson3d_lanes": ("poisson3d", dict(nx=16), None),
    # 108 rows of 128 lanes (not a multiple of 8, so the interpreter's
    # layout alone): ±576 = 4.5 rows, 18 steps
    "poisson3d_steps": ("poisson3d", dict(nx=24), 170_000),
    # ±nx·ny = 8 rows = one whole step: each step reads both neighbours
    "poisson3d_halo": ("poisson3d", dict(nx=32), 230_000),
    "anisotropic": ("poisson3d", dict(nx=16, eps=0.05), 230_000),
}


@pytest.fixture(scope="module")
def cases():
    out = {}
    for name, (kind, kw, budget) in CASES.items():
        p = build_problem(kind, n_nodes=4, precond_block=8, **kw)
        d = Diagonals.from_coo(*p.coo, p.m)
        _, n_rows, lanes = d.data.shape
        extra = {} if budget is None else dict(budget=budget)
        layout = dia_layout(n_rows, lanes, d.offsets, 8, mosaic=False,
                            **extra)
        x = jnp.asarray(np.random.default_rng(len(name)).standard_normal(p.m))
        out[name] = (p, d, layout, x)
    return out


def test_diagonals_hold_the_operator(cases):
    for p, d, _, _ in cases.values():
        np.testing.assert_array_equal(d.to_dense(), p.a.to_dense())
        rows, cols, _ = p.coo
        assert d.offsets == tuple(np.unique(cols - rows))


def test_layouts_split_the_vector_as_asked(cases):
    for name, (_, d, layout, _) in cases.items():
        _, n_rows, lanes = d.data.shape
        steps = n_rows // layout.rows
        assert n_rows % layout.rows == 0 and layout.rows % layout.halo == 0
        assert layout.halo >= halo_rows(d.offsets, lanes)
        assert steps >= (2 if CASES[name][2] is None else 4), (name, layout)


@pytest.mark.parametrize("name", list(CASES))
def test_diagonal_spmv_matches_the_dense_operator(cases, name):
    p, d, layout, x = cases[name]
    want = p.a.to_dense() @ np.asarray(x)
    y = spmv_dia(d.data, x, layout=layout, interpret=True)
    y2, dot = spmv_dia_dot(d.data, x, layout=layout, interpret=True)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-13, atol=1e-13)
    np.testing.assert_array_equal(np.asarray(y2), np.asarray(y))
    np.testing.assert_allclose(float(dot), float(np.asarray(x) @ want),
                               rtol=1e-12)


@pytest.mark.parametrize("name", list(CASES))
def test_batched_member_is_its_lone_run(cases, name):
    p, d, layout, x = cases[name]
    xs = jnp.stack([x, 3.0 * x - 1.0, x * x])
    yb = spmv_dia_batched(d.data, xs, layout=layout, interpret=True)
    yd, dots = spmv_dia_dot_batched(d.data, xs, layout=layout,
                                    interpret=True)
    for b in range(xs.shape[0]):
        y1, dot1 = spmv_dia_dot(d.data, xs[b], layout=layout, interpret=True)
        np.testing.assert_array_equal(np.asarray(yb[b]), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(yd[b]), np.asarray(y1))
        assert float(dots[b]) == float(dot1)


@pytest.mark.parametrize("name", list(CASES))
def test_jnp_mirror_is_bit_identical_to_the_kernel(cases, name):
    """The jnp backend's SpMV+dot repeats the kernel's expression shape:
    equal bits in f64, single and batched."""
    _, d, layout, x = cases[name]
    y, dot = spmv_dia_dot(d.data, x, layout=layout, interpret=True)
    yr, dotr = spmv_dia_dot_ref(d.data, x, layout=layout)
    np.testing.assert_array_equal(np.asarray(yr), np.asarray(y))
    np.testing.assert_array_equal(
        np.asarray(spmv_dia_ref(d.data, x, layout=layout)), np.asarray(y))
    assert float(dotr) == float(dot)
    xs = jnp.stack([x, -x])
    yb, db = spmv_dia_dot_ref_batched(d.data, xs, layout=layout)
    np.testing.assert_array_equal(np.asarray(yb[0]), np.asarray(y))
    assert float(db[0]) == float(dot)
    np.testing.assert_array_equal(
        np.asarray(spmv_dia_ref(d.data, xs, layout=layout)[0]),
        np.asarray(y))


# --------------------------------------------------------------------------- #
# the format the problem bundles choose
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("kind,kw,n_diag", [
    ("poisson2d", dict(nx=16, ny=16), 5),
    ("poisson3d", dict(nx=8), 7),
    ("poisson3d", dict(nx=12, eps=0.1), 7),
])
def test_stencils_choose_diagonals(kind, kw, n_diag):
    p = build_problem(kind, n_nodes=4, **kw)
    layout, mat = problem_spmv(p, "jnp")
    assert len(layout.offsets) == n_diag
    assert mat[0].shape[0] == n_diag
    ops = p.solver_ops("jnp")
    assert (ops.spmv_format, ops.spmv_diagonals) == ("dia", n_diag)
    assert p.solver_ops("jnp", batch=2).spmv_format == "dia"


@pytest.mark.parametrize("bandwidth", [20, 40])
def test_many_diagonals_keep_block_ell(bandwidth):
    """A band of 2·bandwidth + 1 diagonals is more than the diagonal kernel
    unrolls: the bundle keeps Block-ELL, whose data and indices it reads."""
    p = build_problem("banded", n_nodes=4, n=400, bandwidth=bandwidth)
    assert offsets_of(*p.coo[:2], p.m).size == 2 * bandwidth + 1
    for backend in ("jnp", "pallas"):
        layout, mat = problem_spmv(p, backend)
        assert layout is None
        assert mat[0] is p.a.data and mat[1] is p.a.idx
    ops = p.solver_ops("interpret", batch=2)
    assert (ops.spmv_format, ops.spmv_diagonals) == ("blockell", 0)


def test_benchmark_cell_operator_is_stored_by_diagonals():
    """The chip benchmark's 112³ operator, at its 128-wide tiles: seven
    diagonals, 39 MB where the tiles take 3.6 GB. The Block-ELL tiles enter
    the choice by their shape alone, so none is built here."""
    root = Path(__file__).resolve().parents[1] / "bench"
    cfg = json.loads((root / "configs/p3d112_esrp_T20_phi3.json").read_text())
    tile = int(json.loads((root / "layout.json").read_text())["tile"])
    rows, cols, vals, m = poisson3d(cfg["nx"], cfg["ny"], cfg["nz"],
                                    eps=cfg["eps"])
    dtype = np.dtype(cfg["dtype"])
    kmax = 5                  # own, ±1 and ±nx·ny/tile column tiles
    tiles = BlockEll(jax.ShapeDtypeStruct((m // tile, kmax, tile, tile),
                                          dtype),
                     jax.ShapeDtypeStruct((m // tile, kmax), np.int32), None,
                     (m, m), tile, tile)
    problem = Problem(a=tiles, part=Partition(m, cfg["n_nodes"], tile, tile),
                      b=None, pinv_blocks=None, diag_blocks=None,
                      precond_block=cfg["precond_block"],
                      coo=(rows, cols, vals.astype(dtype)))
    layout, (data,) = problem_spmv(problem, "pallas")
    assert layout.offsets == (-12544, -112, -1, 0, 1, 112, 12544)
    assert data.shape == (7, m // 128, 128) and data.dtype == dtype
    assert 2 * layout.halo / layout.rows <= 0.3


@pytest.mark.parametrize("kind,kw,fmt,n_diag", [
    ("poisson3d", dict(nx=8), "dia", 7),
    ("banded", dict(n=400, bandwidth=20), "blockell", 0),
])
def test_solve_reports_its_spmv_format(kind, kw, fmt, n_diag):
    p = build_problem(kind, n_nodes=4, **kw)
    tr = Tracer()
    rep = solve_resilient(p, strategy="esrp", T=5, phi=1, rtol=1e-8,
                          backend="jnp", obs=tr)
    assert (rep.spmv_format, rep.spmv_diagonals) == (fmt, n_diag)
    assert rep.to_json()["spmv_format"] == fmt
    solve = next(n for n in walk_spans(span_tree(tr.events))
                 if n["name"] == "solve")
    assert (solve["args"]["spmv_format"],
            solve["args"]["spmv_diagonals"]) == (fmt, n_diag)


def test_layout_refuses_what_the_kernel_cannot_hold():
    offsets = tuple(range(-20, 21))                 # 41 diagonals
    assert dia_layout(64, 128, offsets, 8, mosaic=True) is None
    # a halo of 64 rows on each side of 64-row steps does not fit 1 KiB
    assert dia_layout(64, 128, (-64 * 128, 0, 64 * 128), 8, mosaic=True,
                      budget=1024) is None
    lay = dia_layout(64, 128, (-1, 0, 1), 8, mosaic=True)
    assert lay == DiaLayout((-1, 0, 1), 32, 8, 32)


def test_chip_layouts_keep_to_the_mosaic_tiling():
    """Compiled for the chip, every block is a multiple of 8 rows of 128
    lanes: a view of 108 rows, or of 64 lanes, has no such layout, which
    the interpreter's has."""
    offsets = (-576, -24, -1, 0, 1, 24, 576)
    assert dia_layout(108, 128, offsets, 4, mosaic=True) is None
    assert dia_layout(216, 64, offsets, 4, mosaic=True) is None
    for n_rows, lanes in ((108, 128), (216, 64)):
        lay = dia_layout(n_rows, lanes, offsets, 4, mosaic=False)
        assert lay is not None and n_rows % lay.rows == 0
    lay = dia_layout(112, 128, offsets, 4, mosaic=True)
    assert lay.rows % 8 == 0 and lay.halo % 8 == 0


def test_chip_bundle_keeps_block_ell_where_mosaic_cannot_block():
    """poisson3d at 24³ is 108 lane rows: the chip's bundle keeps
    Block-ELL, the interpreter's and the jnp bundles store by diagonals."""
    from repro.core.ops import make_problem_ops

    p = build_problem("poisson3d", n_nodes=4, nx=24, precond_block=8)
    assert p.m == 108 * 128
    assert problem_spmv(p, "pallas")[0] is None
    assert make_problem_ops(p, "pallas").spmv_format == "blockell"
    for backend in ("jnp", "interpret"):
        layout, (data,) = problem_spmv(p, backend)
        assert len(layout.offsets) == 7 and data.shape == (7, 108, 128)
        assert make_problem_ops(p, backend).spmv_format == "dia"


def test_backends_share_one_copy_of_the_diagonals():
    """Where the chip's layout and the interpreter's both exist, the
    bundles of every backend read the same diagonals."""
    p = build_problem("poisson3d", n_nodes=4, nx=16, precond_block=8)
    chip, (data,) = problem_spmv(p, "pallas")
    host, (same,) = problem_spmv(p, "jnp")
    assert same is data and data.shape == (7, 32, 128)
    assert chip.rows % 8 == 0 and chip.halo % 8 == 0
    assert chip.offsets == host.offsets
