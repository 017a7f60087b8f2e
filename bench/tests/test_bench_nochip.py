"""The measurement path fails, and prints no result, without a TPU, and in
a directory that holds only the benchmark's own files."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import spec

ARGS = ["--workload", "p3d112_esrp.nofail", "--seed", "4294967311",
        "--seconds", "1", "--trace", "0"]


def run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            pass
    return True


def test_exits_nonzero_without_a_tpu():
    p = run(spec.ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("kept", [("BENCHMARK.json", "bench")])
def test_exits_nonzero_with_only_the_benchmark(tmp_path, kept):
    for name in kept:
        src = spec.ROOT / name
        if src.is_dir():
            shutil.copytree(src, tmp_path / name,
                            ignore=shutil.ignore_patterns("__pycache__"))
        else:
            shutil.copy(src, tmp_path / name)
    p = run(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
