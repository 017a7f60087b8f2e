"""The harness finds every file of a cell by its name in BENCHMARK.json,
and finds files added beside them without an edit of its code."""
from __future__ import annotations

import json
import shutil

import pytest

from bench import spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = spec.resolve(cell)
    assert c.config["strategy"] in ("esrp", "imcr", "none")
    assert set(c.config.get("reduced", {})) == set(next(
        x for x in BENCH["configs"] if x["name"] == c.config_name)["reduced"])
    assert "failures" in c.traffic and "rhs" in c.traffic
    assert spec.operator(c).size(c.config) > 0
    assert {"true_residual_max", "x_error_max", "extra_iters_max",
            "unrecovered"} <= set(spec.limits(c))
    for f in c.traffic["failures"]:
        assert 0 < f["iteration"] and len(f["nodes"]) <= c.config["phi"]
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(spec.metric_reader(c, m["name"]).read)
        if m["name"].endswith("_roofline"):
            k = spec.kernel(c, m["name"][:-len("_roofline")])
            assert k.BOUND == "bytes" and k.NAME


def test_files_added_to_a_copy_are_found(tmp_path):
    shutil.copytree(spec.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = dict(BENCH)
    cfg = json.loads((spec.ROOT / BENCH["configs"][0]["file"]).read_text())
    cfg.update(nx=64, strategy="none")
    (tmp_path / "bench/configs/added.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/added.json").write_text(json.dumps(
        {"rhs": {"distribution": "normal", "pool": 2}, "failures": []}))
    (tmp_path / "bench/metrics/added_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["configs"] = BENCH["configs"] + [dict(
        BENCH["configs"][0], name="added", file="bench/configs/added.json")]
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "added.cell", "config": "added", "traffic": "added",
         "chips": 1, "why": "added as data"}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "added_metric", "unit": "count", "better": "lower",
         "source": "program_counter", "layer": "solver",
         "moves": "time_to_solution_s", "workloads": ["added.cell"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.resolve("added.cell", tmp_path)
    assert c.config["nx"] == 64 and c.config["strategy"] == "none"
    assert c.traffic["rhs"]["pool"] == 2
    assert [m["name"] for m in c.per_layer] == ["added_metric"]
    assert spec.metric_reader(c, "added_metric").read(None) == 42.0
    assert {m["name"] for m in c.end_to_end} == {
        "time_to_solution_s", "setup_s"}
    with pytest.raises(KeyError):
        spec.resolve("no.such.cell", tmp_path)
