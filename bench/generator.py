"""The one traffic generator: right-hand sides and failure schedules from a
traffic file's parameters and the run's seed."""
from __future__ import annotations

import numpy as np


def key(seed: int):
    """A JAX key from any whole number, wider than 32 bits or negative."""
    import jax
    words = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32),
                                    impl="threefry2x32")


def rhs_pool(traffic: dict, operator, cfg: dict, seed: int, m: int, dtype):
    """(window, warm-up) right-hand sides, made on the device.

    The traffic file fixes a set of independent vectors (``set`` of them,
    drawn from its own ``set_seed``), so that every seed gives the window
    the same work. The run's seed orders the set and applies to each vector
    one of the operator's symmetries (a sign and reflections of the grid),
    which leave the work of a solve as it is. The warm-up solve takes the
    first vector of the set as it is drawn."""
    import jax

    spec = traffic["rhs"]
    if spec["distribution"] != "normal":
        raise ValueError(f"rhs distribution {spec['distribution']!r}")
    n = int(spec["set"])
    base = jax.jit(lambda k: jax.random.normal(k, (n, m), dtype))(
        key(spec["set_seed"]))
    rng = np.random.default_rng(np.random.SeedSequence(int(seed) % (1 << 64)))
    order = rng.permutation(n)
    sym = rng.integers(0, operator.symmetries(cfg), n)
    window = [operator.transform(cfg, base[int(i)], int(t))
              for i, t in zip(order, sym)]
    return window, base[0]


def failure_schedule(traffic: dict) -> list[tuple[int, tuple]]:
    """(iteration, nodes) of each failure in one solve, as the traffic file
    states them: the same for every seed."""
    return [(int(f["iteration"]), tuple(int(n) for n in f["nodes"]))
            for f in traffic["failures"]]


def failure_iteration(c: int, t: int, fraction: float, before: int) -> int:
    """The paper's worst case: ``before`` iterations before the end of the
    storage stage (``t`` iterations long) that holds iteration
    ``fraction * c``, where ``c`` is the failure-free iteration count. A
    traffic file's failure iteration is derived so (``bench/calibrate.py``
    prints it)."""
    at = int(c * fraction)
    return (at // t) * t + t - before
