"""Chip benchmark of the resilient PCG solve, driven by ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Each cell names a configuration file (``bench/configs``) and a traffic mix
(``bench/traffic``); each per-layer metric is a reader in ``bench/metrics``
and each kernel's required work is a file in ``bench/kernels``. The harness
finds all of them by the names in ``BENCHMARK.json``.
"""
