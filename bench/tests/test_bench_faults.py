"""A run driven with the timed path broken underneath comes out not correct:
once for each fault a one-chip solve cell can have, and for each way a
fail-stop's recovery can hand back a wrong or approximate state. The look
for a chip is skipped; the rest of the run is the benchmark's own, at a
tiny size, held to the chip cells' limits."""
from __future__ import annotations

import contextlib
import io
import json

import pytest

from bench import faults, harness
from bench.tests.conftest import TINY, TINY_IMCR

SEED = "3000000019"


def drive(root, cell, monkeypatch, seconds=0.3, trace=0, seed=SEED):
    monkeypatch.setattr(harness, "use_cache", lambda root: "off")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", seed,
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_chip=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.fixture
def planted():
    undo = []
    yield lambda fault: undo.append(faults.plant(fault))
    for u in undo:
        u()


@pytest.mark.parametrize("cell", [f"{TINY}.nofail", f"{TINY}.tiny_failstop",
                                  f"{TINY_IMCR}.tiny_failstop"])
def test_sound_run_is_correct(tiny_root, monkeypatch, cell):
    r = drive(tiny_root, cell, monkeypatch)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 1
    assert list(r)[-1] == "checks"
    for k, c in r["checks"].items():
        assert c["value"] <= c["limit"], k
    assert set(r["metrics"]) == {"time_to_solution_s", "setup_s"}
    assert r["device"]["platform"] == "cpu"


def test_traced_run_reports_the_layer_metrics(tiny_root, monkeypatch):
    r = drive(tiny_root, f"{TINY}.tiny_failstop", monkeypatch, trace=1)
    assert r["correct"] is True
    # no TPU kernels on the CPU: the roofline readers find nothing to read
    assert set(r["metrics"]) == {"device_idle_pct", "iters_per_solve",
                                 "dispatches_per_solve", "recovery_s"}
    assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
    assert r["breakdown"]["device_ops"] and r["breakdown"]["idle_gaps"]


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered",
                                   "half_left_out"])
@pytest.mark.parametrize("traffic", ["nofail", "tiny_failstop"])
def test_fault_is_not_correct(tiny_root, monkeypatch, planted, fault,
                              traffic):
    planted(fault)
    r = drive(tiny_root, f"{TINY}.{traffic}", monkeypatch)
    assert r["correct"] is False and r["failed"] >= 1


def test_failure_that_never_struck_is_not_correct(tiny_root, monkeypatch,
                                                  planted):
    planted("failure_skipped")
    r = drive(tiny_root, f"{TINY}.tiny_failstop", monkeypatch)
    assert r["correct"] is False
    assert r["checks"]["unrecovered"]["value"] >= 1


@pytest.mark.parametrize("fault", faults.RECOVERY)
@pytest.mark.parametrize("config", [TINY, TINY_IMCR])
def test_wrong_recovery_is_not_correct(tiny_root, monkeypatch, planted,
                                       fault, config):
    planted(fault)
    r = drive(tiny_root, f"{config}.tiny_failstop", monkeypatch)
    assert r["correct"] is False and r["failed"] >= 1


def test_recovery_to_another_iteration_is_not_correct():
    cfg = {"T": 20, "stored_iteration_offset": 1}
    assert harness.stored_iteration(cfg, 158) == 141
    assert harness.stored_iteration(dict(cfg, stored_iteration_offset=0),
                                    158) == 140
    assert harness.stored_iteration(cfg, 20) == -1
    assert harness.stored_iteration(cfg, 21) == 21
