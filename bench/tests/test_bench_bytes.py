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
    assert m == 2_097_152 and nnz == 7 * m - 6 * 128 * 128
    g = {"m": m, "nnz": nnz, "block": 8, "itemsize": 4}
    assert SPMV_DOT.required_bytes(g) == nnz * 8 + 2 * m * 4
    assert SPMV_DOT.required_bytes(g) == pytest.approx(133e6, rel=0.01)
    assert UPDATE.required_bytes(g) == 7 * m * 4 + (m // 8) * 36 * 4
    assert UPDATE.required_bytes(g) == pytest.approx(96.5e6, rel=0.01)
    assert SPMV_DOT.BOUND == UPDATE.BOUND == "bytes"


def test_peaks_by_device_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks("source")
