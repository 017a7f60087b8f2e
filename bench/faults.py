"""Faults planted under the timed path: the tests drive a run with each one
and see ``correct`` come out false, and ``bench/calibrate.py`` reads the
recovery faults on the chip, at a cell's own size, for the upper readings
of the limits. The benchmark's own runs never plant one.

Answer faults break what the program's entry returns:

- ``state_unchanged``: the iterate never moves (x stays 0);
- ``answer_altered``: one entry of x changed where it is produced;
- ``half_left_out``: every other solve returns no answer;
- ``failure_skipped``: the scheduled fail-stop never strikes.

Recovery faults break the state that a fail-stop's recovery hands back,
ESRP's Alg. 2 reconstruction or IMCR's buddy restore alike:

- ``lost_share_zeroed``: the failed nodes' share of x comes back as zeros,
  as if its reconstruction were never written back; r, z and p come back
  exactly, so the solve runs its normal course to a wrong answer. (With r,
  z and p zeroed too the solve goes to NaN and runs to its iteration cap,
  which every check catches.)
- ``direction_restarted``: x, r and z come back exactly, but the search
  direction p comes back as z, as if its history were lost: a restart of
  CG. The state stays consistent (r = b - A x), so the solve still
  converges to a right answer, only later: an approximate recovery.
"""
from __future__ import annotations

ANSWER = ("state_unchanged", "answer_altered", "half_left_out",
          "failure_skipped")
RECOVERY = ("lost_share_zeroed", "direction_restarted")
FAULTS = ANSWER + RECOVERY


def _answer(real, fault):
    import jax.numpy as jnp

    calls = {"n": 0}

    def wrapped(*args, **kwargs):
        rep = real(*args, **kwargs)
        calls["n"] += 1
        if fault == "state_unchanged":
            rep.x = jnp.zeros_like(rep.x)
        elif fault == "answer_altered":
            rep.x = rep.x.at[rep.x.shape[-1] // 2].add(1e-2)
        elif fault == "half_left_out" and calls["n"] % 2:
            rep.x = None
        elif fault == "failure_skipped":
            rep.events = []
        return rep

    return wrapped


def _lose(st, part, failed, fault):
    """``st`` with its recovered PCG state broken as ``fault`` says."""
    from repro.core.failures import failed_row_mask, zero_failed

    if fault == "direction_restarted":
        return st._replace(pcg=st.pcg._replace(p=st.pcg.z))
    mask = failed_row_mask(part, failed)
    return st._replace(pcg=st.pcg._replace(x=zero_failed(st.pcg.x, mask)))


def plant(fault: str):
    """Plant ``fault`` in the program for this process; returns the undo."""
    from repro.core import driver

    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    saved = {n: getattr(driver, n) for n in
             ("solve_resilient", "_esrp_failure", "_imcr_failure")}
    if fault in ANSWER:
        driver.solve_resilient = _answer(saved["solve_resilient"], fault)
    else:
        esrp_real, imcr_real = saved["_esrp_failure"], saved["_imcr_failure"]

        def esrp_failure(problem, plan, st, failed, *args, **kwargs):
            out = esrp_real(problem, plan, st, failed, *args, **kwargs)
            return (_lose(out[0], problem.part, failed, fault),) + out[1:]

        def imcr_failure(st, part, failed, *args, **kwargs):
            out = imcr_real(st, part, failed, *args, **kwargs)
            return (_lose(out[0], part, failed, fault),) + out[1:]

        driver._esrp_failure = esrp_failure
        driver._imcr_failure = imcr_failure

    def undo():
        for n, f in saved.items():
            setattr(driver, n, f)
    return undo
