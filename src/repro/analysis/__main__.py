"""Entry point for ``python -m repro.analysis``.

The analyzer only traces, so it runs on the CPU and never takes a chip.
It forces 8 host-platform devices (matching the subprocess convention of
the 8-device mesh test suites) so the ``sharded.*.8dev`` entries are
analyzable on any box — but only if jax has not been imported yet and the
caller did not pin the flag themselves.
"""
import os
import sys

if "jax" not in sys.modules:
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8").strip()

from repro.analysis.cli import main

if __name__ == "__main__":
    sys.exit(main())
