"""Solver telemetry: program spans, sync-free iteration metrics, nested
lifecycle spans, Chrome-trace/Perfetto + JSONL exporters, and HLO roofline
pricing for the CPU benchmarks.

Every driver and recovery phase is a ``span``: a
``jax.profiler.TraceAnnotation`` named ``repro.<phase>``, which costs nothing
unless a profiler records (``jax.profiler.trace``), and then puts the phase
on the host thread beside the device ops. The durations of a few of them are
report fields (``SolveReport.runtime_s``, ``prologue_s``, ``recovery_s``;
``EventReport.event_s``).

The rest is strictly opt-in: the solver's hot loop compiles with zero added
ops unless a ``Tracer`` is threaded through ``solve_resilient(obs=...)``
(asserted at jaxpr level in tests/test_obs.py). With a tracer attached:

  * every chunk's norm readback also carries a small on-device metrics ring
    (per-iteration ||r||, rz, storage-push/star flags, the orthogonality
    invariant residual) — a full convergence/event history at zero extra
    dispatches;
  * solver lifecycle phases (chunk dispatch/settle, storage pushes, failure
    injection, the Alg. 2 recovery broken into its line-5/6/8 inner phases
    plus the queue fetch, SDC detect -> repair, elastic re-partition) land as
    nested wall-time spans with byte counters from ``aspmv.RedundancyPlan``
    and ``core.tiers``, and each Alg. 2 line waits on the device before its
    span closes.

``solver_rooflines`` prices a SolverOps bundle's lowered HLO with the seed
analyzer (``roofline/hlo_analysis``); ``benchmarks/run.py`` embeds it in its
BENCH_*.json.
"""
from repro.obs.export import (chrome_trace, metrics_snapshot, span_tree,
                              validate_chrome_trace, walk_spans,
                              write_chrome_trace, write_jsonl)
from repro.obs.rooflines import kernel_roofline, solver_rooflines
from repro.obs.trace import SCHEMA_VERSION, Span, Tracer, jsonable, span

__all__ = [
    "SCHEMA_VERSION", "Span", "Tracer", "jsonable", "span",
    "chrome_trace", "write_chrome_trace", "write_jsonl",
    "validate_chrome_trace", "span_tree", "walk_spans", "metrics_snapshot",
    "kernel_roofline", "solver_rooflines",
]
