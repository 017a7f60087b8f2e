"""Required bytes of the kernels, counted from the operator, and the plain
reference operator against the program's."""
from __future__ import annotations

import numpy as np
import pytest

from bench import peaks, spec
from bench.spec import load_module

OPERATOR = load_module(spec.ROOT / "bench/operators/poisson3d.py")
SPMV_DOT = load_module(spec.ROOT / "bench/kernels/spmv_dot.py")
UPDATE = load_module(spec.ROOT / "bench/kernels/fused_update.py")


@pytest.mark.parametrize("dims", [(8, 8, 8), (12, 6, 5), (16, 16, 16)])
def test_nnz_and_operator_match_the_program_coo(dims):
    from repro.sparse.matrices import poisson3d

    nx, ny, nz = dims
    cfg = {"nx": nx, "ny": ny, "nz": nz, "eps": 1.0}
    rows, cols, vals, n = poisson3d(nx, ny, nz)
    assert OPERATOR.size(cfg) == n
    assert OPERATOR.nnz(cfg) == rows.size
    a = OPERATOR.csr(cfg).toarray()
    b = np.zeros((n, n))
    np.add.at(b, (rows, cols), vals)
    np.testing.assert_array_equal(a, b)


def test_required_bytes_at_128():
    cfg = {"nx": 128}
    m, nnz = OPERATOR.size(cfg), OPERATOR.nnz(cfg)
    upper = OPERATOR.nnz_upper(cfg)
    assert m == 2_097_152 and nnz == 7 * m - 6 * 128 * 128
    assert upper == 4 * m - 3 * 128 * 128 and OPERATOR.SYMMETRIC
    g = {"m": m, "nnz": nnz, "nnz_upper": upper, "symmetric": True,
         "block": 8, "itemsize": 4}
    assert SPMV_DOT.required_bytes(g) == (upper + 2 * m) * 4
    assert SPMV_DOT.required_bytes(g) == pytest.approx(50.1e6, rel=0.01)
    assert SPMV_DOT.required_bytes({**g, "symmetric": False}) \
        == (nnz + 2 * m) * 4
    assert UPDATE.required_bytes(g) == 7 * m * 4 + (m // 8) * 36 * 4
    assert UPDATE.required_bytes(g) == pytest.approx(96.5e6, rel=0.01)
    assert SPMV_DOT.BOUND == UPDATE.BOUND == "bytes"


@pytest.mark.parametrize("dims", [(8, 8, 8), (12, 6, 5), (16, 16, 16)])
def test_nnz_upper_counts_the_symmetric_operator_once(dims):
    import scipy.sparse as sp

    nx, ny, nz = dims
    cfg = {"nx": nx, "ny": ny, "nz": nz, "eps": 1.0}
    a = OPERATOR.csr(cfg)
    assert OPERATOR.nnz_upper(cfg) == sp.triu(a).nnz
    assert OPERATOR.SYMMETRIC and (a != a.T).nnz == 0


def _storage_bytes(kind: str, a) -> int:
    """Bytes of the matrix ``a`` alone in one storage, float32 values and
    int32 indices."""
    import scipy.sparse as sp

    if kind == "csr":
        return a.nnz * (4 + 4) + (a.shape[0] + 1) * 4
    if kind == "block_ell":
        from repro.sparse.blockell import BlockEll

        coo = a.tocoo()
        e = BlockEll.from_coo(coo.row, coo.col, coo.data, a.shape[0],
                              128, 128, dtype=np.float32)
        return int(e.data.nbytes + e.idx.nbytes + e.nblk.nbytes)
    d = sp.dia_matrix(sp.triu(a) if kind == "dia_upper" else a)
    assert d.offsets.size == {"dia": 7, "dia_upper": 4}[kind]
    return d.data.size * 4 + d.offsets.size * 4


@pytest.mark.parametrize("kind", ["csr", "block_ell", "dia", "dia_upper"])
def test_required_bytes_is_a_floor_for_every_storage(kind):
    """The count is at or below what CSR, the program's 128x128 Block-ELL,
    7-diagonal and symmetric 4-diagonal storage move for one call at 16³,
    each with one read of x and one write of y."""
    cfg = {"nx": 16}
    m = OPERATOR.size(cfg)
    g = {"m": m, "nnz": OPERATOR.nnz(cfg),
         "nnz_upper": OPERATOR.nnz_upper(cfg),
         "symmetric": OPERATOR.SYMMETRIC, "block": 8, "itemsize": 4}
    moved = _storage_bytes(kind, OPERATOR.csr(cfg)) + 2 * m * 4
    assert SPMV_DOT.required_bytes(g) <= moved


def test_peaks_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("source")
