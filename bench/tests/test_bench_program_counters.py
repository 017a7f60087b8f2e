"""The readers of the program's own span and counter readings: a traced
run reports each where its ``workloads`` names the cell and leaves it out
elsewhere, and each reads nothing from a program that lacks the reading."""
from __future__ import annotations

import contextlib
import io
import json
from types import SimpleNamespace

import pytest

from bench import harness, spec
from bench.tests.conftest import TINY, TINY_IMCR, chip_cell, tiny_copy

SEED = "2147483659"
# per-layer entries of the readers, with the chip cells they read in
ENTRIES = [
    {"name": "event_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "recovery",
     "moves": "time_to_solution_s",
     "workloads": [("esrp", "failstop"), ("imcr", "failstop")]},
    {"name": "alg2_inner_iters", "unit": "iters", "better": "lower",
     "source": "program_counter", "layer": "recovery",
     "moves": "time_to_solution_s", "workloads": [("esrp", "failstop")]},
    {"name": "prologue_s", "unit": "s", "better": "lower",
     "source": "program_span", "layer": "driver",
     "moves": "time_to_solution_s",
     "workloads": [("esrp", "nofail"), ("esrp", "failstop"),
                   ("imcr", "failstop")]},
]
# the tiny cell of conftest.tiny_copy that stands for each chip cell
TINY_CELLS = {("esrp", "nofail"): f"{TINY}.nofail",
              ("esrp", "failstop"): f"{TINY}.tiny_failstop",
              ("imcr", "failstop"): f"{TINY_IMCR}.tiny_failstop"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A tiny copy of the benchmark with the entries added, each listing
    its chip cells and the tiny cells that stand for them."""
    dest = tiny_copy(tmp_path_factory.mktemp("bench_counters"))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    for e in ENTRIES:
        bench["per_layer"].append(dict(e, workloads=[
            name for w in e["workloads"]
            for name in (chip_cell(*w), TINY_CELLS[w])]))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


def drive(root, cell, monkeypatch) -> dict:
    monkeypatch.setattr(harness, "use_cache", lambda root: "off")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(["--workload", cell, "--seed", SEED, "--seconds",
                           "0.3", "--trace", "1"], root=root,
                          require_chip=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_traced_run_reports_the_readings_where_listed(root, monkeypatch,
                                                     cell):
    r = drive(root, TINY_CELLS[cell], monkeypatch)
    assert r["correct"] is True
    got = r["metrics"]
    for e in ENTRIES:
        assert (e["name"] in got) == (cell in e["workloads"]), e["name"]
    assert got["prologue_s"]["value"] > 0
    if "event_s" in got:
        assert got["event_s"]["value"] >= got["recovery_s"]["value"] > 0
    if "alg2_inner_iters" in got:
        assert got["alg2_inner_iters"]["value"] > 0


@pytest.mark.parametrize("name", [e["name"] for e in ENTRIES])
def test_reader_reads_nothing_without_the_reading(name):
    """The reports of an older program, which lacks the counters, give no
    value and raise nothing."""
    event = SimpleNamespace(kind="fail-stop", iter=38, nodes=(1, 2),
                            target_iter=36, recovery_s=0.1)
    old = SimpleNamespace(events=[event], recovery_s=0.1, run_calls=3)
    reader = spec.metric_reader(spec.resolve(chip_cell("esrp", "failstop")),
                                name)
    for reports in ([old], []):
        ctx = SimpleNamespace(reports=lambda reports=reports: reports)
        assert reader.read(ctx) is None
