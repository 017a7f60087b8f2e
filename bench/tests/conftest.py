"""Fixtures of the benchmark's own tests. None of them touches a TPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

from bench import spec

TINY = "tiny"
TINY_IMCR = "tiny_imcr"


def chip_cell(strategy: str, traffic: str) -> str:
    """The name of the chip cell of ``strategy`` under ``traffic``."""
    return next(w for w in (spec.resolve(w["name"]) for w in json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["workloads"])
        if w.config["strategy"] == strategy
        and w.traffic_name == traffic).name


def tiny_copy(dest: Path) -> Path:
    """A copy of the benchmark with tiny cells added as data: poisson3d at
    24^3 on 4 nodes, the jnp backend, 8x8 tiles; ESRP under each traffic
    mix, and IMCR through the failures, each held to the limits of the
    chip cell it stands for."""
    shutil.copytree(spec.ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", dest)
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    tiny = dict(nx=24, ny=24, nz=24, n_nodes=4, T=5, phi=2)
    for name, strategy in ((TINY, "esrp"), (TINY_IMCR, "imcr")):
        base = next(c for c in bench["configs"]
                    if json.loads((spec.ROOT / c["file"]).read_text())[
                        "strategy"] == strategy)
        cfg = json.loads((spec.ROOT / base["file"]).read_text())
        cfg.update(tiny)
        (dest / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
        bench["configs"].append(dict(base, name=name,
                                     file=f"bench/configs/{name}.json"))
    fail = json.loads((dest / "bench/traffic/failstop.json").read_text())
    fail["failures"][0].update(nodes=[1, 2], iteration=38)
    (dest / "bench/traffic/tiny_failstop.json").write_text(json.dumps(fail))
    for config, traffic, like in ((TINY, "nofail", ("esrp", "nofail")),
                                  (TINY, "tiny_failstop", ("esrp", "failstop")),
                                  (TINY_IMCR, "tiny_failstop",
                                   ("imcr", "failstop"))):
        name = f"{config}.{traffic}"
        like = chip_cell(*like)
        shutil.copy(dest / f"bench/limits/{like}.json",
                    dest / f"bench/limits/{name}.json")
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "tiny cell of the tests"})
        for m in bench["per_layer"]:
            m.get("workloads", []).append(name)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    layout = json.loads((dest / "bench/layout.json").read_text())
    layout.update(backend="jnp", tile=8)
    (dest / "bench/layout.json").write_text(json.dumps(layout))
    return dest


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return tiny_copy(tmp_path_factory.mktemp("bench_copy"))
