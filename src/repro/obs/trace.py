"""Span tracer + per-iteration metrics accumulator (Chrome-trace event model).

One ``Tracer`` instance accumulates, in memory, an ordered list of Chrome
trace events (``ph`` in B/E/C/i — the subset Perfetto renders), a metadata
dict (roofline attributions, redundancy-plan volumes), cumulative byte/count
counters, and the per-iteration metric history assembled from the chunked
driver's readbacks. Exporters live in ``repro.obs.export``.

Conventions:
  * timestamps are microseconds since tracer creation, strictly increasing
    (two events within the clock's resolution are nudged apart by 1 ns so
    the exported trace is always sorted — a validator requirement);
  * span ``args`` may be mutated while the span is open (``sp.args[...] =``);
    the final values land on the closing "E" event — how the driver attaches
    results (converged?, fetch bytes, inner residuals) to a phase it opened
    before knowing them;
  * per-iteration metrics are stamped at *readback* time, not at iteration
    time: the sync-free protocol reads a whole chunk's ring in one host
    sync, so rows share the settle timestamp and carry the true iteration
    index in their args.

``jsonable`` is the single serialization path shared by the trace exporters,
the JSONL event log, and the report ``to_json`` methods (driver satellite):
device/numpy scalars coerce to Python, arrays to lists, NaN/inf to None.

``span`` is the one way the program marks a phase: always a
``jax.profiler.TraceAnnotation`` named ``repro.<name>`` (so a profile shows
the phase on the host thread, on the profiler's clock beside the device
ops), and a ``Tracer`` span too when one is attached.
"""
from __future__ import annotations

import contextlib
import math
import time

import jax
import numpy as np

SCHEMA_VERSION = 1


def jsonable(obj):
    """Coerce ``obj`` to JSON-safe types (NaN/inf -> None, numpy/device
    scalars -> Python, arrays/tuples/sets -> lists, dict keys -> str)."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return obj
    if isinstance(obj, float):
        return obj if math.isfinite(obj) else None
    if isinstance(obj, dict):
        return {str(k): jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple, set)):
        return [jsonable(v) for v in obj]
    if isinstance(obj, np.integer):
        return int(obj)
    if isinstance(obj, (np.floating, np.bool_)):
        return jsonable(obj.item())
    if isinstance(obj, np.ndarray):
        return [jsonable(v) for v in obj.tolist()]
    if hasattr(obj, "__array__"):           # jax.Array (incl. 0-d scalars)
        return jsonable(np.asarray(obj))
    return str(obj)


class Span:
    """Handle for one (possibly still open) span. ``args`` is mutable while
    open; ``dur_s`` is None until the span closes."""

    __slots__ = ("name", "cat", "args", "t0_us", "t1_us")

    def __init__(self, name: str, cat: str, args: dict, t0_us: float):
        self.name = name
        self.cat = cat
        self.args = args
        self.t0_us = t0_us
        self.t1_us: float | None = None

    @property
    def dur_s(self) -> float | None:
        return None if self.t1_us is None else (self.t1_us - self.t0_us) / 1e6

    def __repr__(self):  # pragma: no cover - debugging aid
        state = "open" if self.t1_us is None else f"{self.dur_s:.6f}s"
        return f"Span({self.name!r}, {self.cat!r}, {state})"


class Tracer:
    """Accumulates spans, counters, instants, and iteration metrics."""

    def __init__(self, name: str = "solve"):
        self.name = name
        self._clock0 = time.perf_counter()
        self._last_us = 0.0
        self.events: list[dict] = []      # Chrome trace events, ts-ordered
        self.records: list[dict] = []     # non-trace JSONL records (reports)
        self.meta: dict = {"schema_version": SCHEMA_VERSION, "tracer": name}
        self.counters: dict[str, float] = {}
        self._stack: list[Span] = []
        self._hist_iter: list[int] = []
        self._hist: dict[str, list] = {}

    # ------------------------------------------------------------------ #
    def _ts(self) -> float:
        us = (time.perf_counter() - self._clock0) * 1e6
        if us <= self._last_us:            # clock resolution tie: nudge 1 ns
            us = self._last_us + 1e-3
        self._last_us = us
        return us

    # -- spans --------------------------------------------------------- #
    def begin(self, name: str, cat: str = "solver", **args) -> Span:
        """Open a span (explicit form — pair with ``end``/``close``)."""
        sp = Span(name, cat, dict(args), self._ts())
        self.events.append(dict(name=name, cat=cat, ph="B", ts=sp.t0_us,
                                pid=0, tid=0, args=jsonable(sp.args)))
        self._stack.append(sp)
        return sp

    def end(self, **args) -> Span:
        """Close the innermost open span; ``args`` merge into its ``args``."""
        sp = self._stack.pop()
        sp.args.update(args)
        sp.t1_us = self._ts()
        self.events.append(dict(name=sp.name, cat=sp.cat, ph="E", ts=sp.t1_us,
                                pid=0, tid=0, args=jsonable(sp.args)))
        return sp

    def close(self, sp: Span, **args) -> Span:
        """Close ``sp``, first closing anything still nested inside it (an
        exception may have unwound past inner ``begin``s)."""
        while self._stack and self._stack[-1] is not sp:
            self.end()
        return self.end(**args) if self._stack else sp

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "solver", **args):
        sp = self.begin(name, cat, **args)
        try:
            yield sp
        finally:
            self.close(sp)

    @property
    def current(self) -> Span | None:
        """The innermost open span."""
        return self._stack[-1] if self._stack else None

    # -- points -------------------------------------------------------- #
    def instant(self, name: str, cat: str = "solver", **args) -> None:
        self.events.append(dict(name=name, cat=cat, ph="i", s="t",
                                ts=self._ts(), pid=0, tid=0,
                                args=jsonable(args)))

    def counter(self, name: str, **values) -> None:
        """Sampled counter event (one Chrome counter track per args key)."""
        self.events.append(dict(name=name, cat="counter", ph="C",
                                ts=self._ts(), pid=0, tid=0,
                                args=jsonable(values)))

    def add_counter(self, name: str, delta, **args) -> float:
        """Cumulative counter: bump the running total and emit it."""
        cur = self.counters.get(name, 0) + delta
        self.counters[name] = cur
        payload = dict(value=cur, **args)
        self.events.append(dict(name=name, cat="counter", ph="C",
                                ts=self._ts(), pid=0, tid=0,
                                args=jsonable(payload)))
        return cur

    def record(self, kind: str, payload) -> None:
        """Append a non-trace record (e.g. a SolveReport) for the JSONL log."""
        self.records.append(dict(type=kind, ts=self._ts(),
                                 data=jsonable(payload)))

    # -- iteration metrics --------------------------------------------- #
    def record_iters(self, iters, **columns) -> None:
        """Append one chunk's per-iteration metric rows (already trimmed to
        the executed count by the caller). ``iters`` are the executed
        iteration indices; each column is a same-length array. Also emits
        one counter event per iteration so the history renders as Perfetto
        counter tracks."""
        idx = np.asarray(iters, np.int64)
        self._hist_iter.extend(int(j) for j in idx)
        cols = {k: np.asarray(v) for k, v in columns.items()}
        for k, v in cols.items():
            if v.shape[0] != idx.shape[0]:
                raise ValueError(f"column {k!r}: {v.shape[0]} rows for "
                                 f"{idx.shape[0]} iterations")
            self._hist.setdefault(k, []).extend(v.tolist())
        for row in range(idx.shape[0]):
            self.events.append(dict(
                name="iteration", cat="metrics", ph="C", ts=self._ts(),
                pid=0, tid=0,
                args=jsonable({"iter": int(idx[row]),
                               **{k: v[row] for k, v in cols.items()}})))

    def iter_history(self) -> dict:
        """The accumulated per-iteration history as numpy columns, sorted by
        iteration with later duplicates winning (a rollback re-executes a
        stretch; the re-run's values are the ones the solve continued from).
        """
        it = np.asarray(self._hist_iter, np.int64)
        last_pos: dict[int, int] = {}
        for pos, j in enumerate(it.tolist()):
            last_pos[j] = pos
        keep = np.asarray([last_pos[j] for j in sorted(last_pos)], np.int64)
        out = {"iter": it[keep] if it.size else it}
        for k, v in self._hist.items():
            arr = np.asarray(v)
            out[k] = arr[keep] if it.size else arr
        return out


@contextlib.contextmanager
def span(name: str, cat: str = "solver", tracer: Tracer | None = None,
         **args):
    """Mark one program phase; yields its ``Span``, whose ``dur_s`` is the
    phase's host-clock duration once the block has left.

    The phase is always a ``jax.profiler.TraceAnnotation`` named
    ``repro.<name>``: free unless a profiler is recording, and then a host
    span on the profiler's clock, on the thread that ran it. With a
    ``tracer`` it is also a span of that Tracer, under ``name`` with
    ``args``. Without one nothing else happens: ``args`` are kept on the
    yielded Span and never serialized, its ``t0_us``/``t1_us`` are
    ``time.perf_counter`` microseconds, and nothing waits on the device. So
    a phase that only dispatches device work times the host's dispatch,
    not the device; callers that want device time either end the phase on
    a read-back or sync only when a tracer is attached.
    """
    with jax.profiler.TraceAnnotation("repro." + name):
        if tracer is not None:
            with tracer.span(name, cat, **args) as sp:
                yield sp
            return
        sp = Span(name, cat, args, time.perf_counter() * 1e6)
        try:
            yield sp
        finally:
            sp.t1_us = time.perf_counter() * 1e6
