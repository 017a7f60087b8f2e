"""The lower-precision controls come out not correct by each cell's own
check and limits, where the reference solve at the stated precision passes:
the plain reference put in the program's place, judged by ``harness.check``
as a run judges the program. Each cell's limits file names the controls
that it fails. Run at 32^3, a size a test can hold; on the chip the same
comparison is made at the cell's own size by bench/calibrate.py."""
from __future__ import annotations

import dataclasses
import json

import numpy as np
import pytest

from bench import generator, harness, reference, spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SEEDS = (1, 2, 3)


def judge(name: str) -> dict:
    """{precision: [correct, one per seed]} of the reference solve at each
    precision in the cell's place, at 32^3."""
    cell = spec.resolve(name)
    cell = dataclasses.replace(cell, config=dict(cell.config, nx=32, ny=32,
                                                 nz=32))
    cfg, op = cell.config, spec.operator(cell)
    ref = harness.Reference.build(cell)
    failures = generator.failure_schedule(cell.traffic)
    out = {}
    for seed in SEEDS:
        pool, _ = generator.rhs_pool(cell.traffic, op, cfg, seed,
                                     op.size(cfg), np.float32)
        keys = [(seed, k) for k in range(len(pool))]
        for prec in reference.PRECISIONS:
            solves = []
            for k, b in enumerate(pool):
                x, it, rel = reference.solve(op, cfg, ref.pinv, b,
                                             float(cfg["rtol"]), prec)
                assert rel < cfg["rtol"] and 0 < it < 1000
                solves.append(harness.Solve(k, 0.0, harness.control_report(
                    x, it, rel, failures, cfg)))
            numbers, _ = harness.check(cell, solves, pool, failures, ref,
                                       keys)
            out.setdefault(prec, []).append(
                all(v <= lim for v, lim in numbers.values()))
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_and_reference_passes(cell):
    named = json.loads((spec.ROOT / f"bench/limits/{cell}.json").read_text())[
        "control_fails"]
    assert "default" in named
    correct = judge(cell)
    assert all(correct[spec.resolve(cell).config["matmul_precision"]])
    for prec in reference.PRECISIONS:
        if prec in named:
            assert not any(correct[prec]), prec



def test_high_lies_between_highest_and_default():
    """The bf16_3x emulation loses bits that the reference keeps and keeps
    bits that one bfloat16 pass loses, by the true residual at 32^3."""
    cfg = dict(spec.resolve(CELLS[0]).config, nx=32, ny=32, nz=32)
    op = spec.load_module(spec.ROOT / "bench/operators/poisson3d.py")
    a = op.csr(cfg)
    pinv = np.asarray(reference.block_jacobi_inverse(a, 8), np.float32)
    out = {}
    for seed in SEEDS:
        b = np.random.default_rng(seed).standard_normal(a.shape[0]).astype(
            np.float32)
        for prec in reference.PRECISIONS:
            x, _, _ = reference.solve(op, cfg, pinv, b, cfg["rtol"], prec)
            out.setdefault(prec, []).append(reference.true_residual(a, x, b))
    assert max(out["highest"]) < min(out["high"])
    assert max(out["high"]) < min(out["default"])
