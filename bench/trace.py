"""Reduce a JAX profiler trace to device busy time, idle gaps and time per
device operation.

A trace is read with ``jax.profiler.ProfileData`` from the ``.xplane.pb``
that ``jax.profiler.start_trace`` writes. Device operations are the events
of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; host spans are
the events of the other lines of the ``/host:CPU`` plane.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Callable, Iterable

Interval = tuple[float, float]          # (start, end) in ns
CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)?$")


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float

    @property
    def container(self) -> bool:
        """A control-flow op, whose interval holds the ops of its body."""
        return bool(CONTAINER.match(self.name))

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def is_tpu_op_line(plane: str, line: str) -> bool:
    return plane.startswith("/device:TPU:") and line == "XLA Ops"


def is_cpu_op_line(plane: str, line: str) -> bool:
    """XLA:CPU runs its programs on the client's threads: the stand-in for
    a device where tests record a trace without one."""
    return plane == "/host:CPU" and line.startswith("tf_XLAPjRtCpuClient")


def device_lines(platform: str) -> Callable[[str, str], bool]:
    return {"tpu": is_tpu_op_line, "cpu": is_cpu_op_line}[platform]


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def short(name: str) -> str:
    """A device op's HLO instruction name: the text before `` = ``."""
    return name.split(" = ", 1)[0]


def events(profile, select: Callable[[str, str], bool]
           ) -> dict[tuple[str, str], list]:
    """Events of the lines ``select(plane, line)`` picks, per line."""
    out: dict[tuple[str, str], list] = {}
    for plane in profile.planes:
        for line in plane.lines:
            if select(plane.name, line.name):
                out[(plane.name, line.name)] = [
                    Event(short(e.name), e.start_ns,
                          e.start_ns + e.duration_ns) for e in line.events]
    return out


def union(intervals: Iterable[Interval], lo: float, hi: float
          ) -> list[Interval]:
    """Merge intervals, clipped to [lo, hi]."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The idle intervals of [lo, hi] around merged busy intervals."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_seconds(ops: list[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(((o.start, o.end) for o in ops),
                                       lo, hi)) * 1e-9


def op_seconds(ops: list[Event], lo: float, hi: float
               ) -> dict[str, tuple[float, int]]:
    """Summed device seconds and event count per operation name, for the
    events that start inside [lo, hi]. Control-flow ops are left out: their
    time is their body's."""
    out: dict[str, list] = {}
    for o in ops:
        if lo <= o.start < hi and not o.container:
            acc = out.setdefault(o.name, [0.0, 0])
            acc[0] += o.seconds
            acc[1] += 1
    return {k: (v[0], v[1]) for k, v in out.items()}


def matching(table: dict[str, tuple[float, int]], pattern: str
             ) -> tuple[float, int]:
    """Seconds and count summed over the operation names ``pattern``
    matches (``re.search``)."""
    rx = re.compile(pattern)
    hits = [v for k, v in table.items() if rx.search(k)]
    return sum(h[0] for h in hits), sum(h[1] for h in hits)


def label(gap: Interval, host: list[Event]) -> str:
    """What the host was doing in an idle gap: the shortest host span that
    covers the gap's middle."""
    mid = 0.5 * (gap[0] + gap[1])
    covering = [h for h in host if h.start <= mid <= h.end]
    if not covering:
        return "(no host span)"
    return min(covering, key=lambda h: h.end - h.start).name


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over the device planes
    ops: dict[str, tuple[float, int]]  # summed over the device planes
    idle: list[tuple[str, float]]      # longest gaps first, first device,
    #                                    named by the host span they fall in

    def top_ops(self, n: int = 10) -> list[list]:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:n]
        return [[k, v[0]] for k, v in top]


def summarize(profile, window: str,
              device: Callable[[str, str], bool] = is_tpu_op_line) -> Summary:
    """Busy and idle time of the device planes inside the host span named
    ``window`` (the measured window's annotation)."""
    def host(plane: str, line: str) -> bool:
        return plane.startswith("/host:") and not device(plane, line)

    thread = [evs for evs in events(profile, host).values()
              if any(e.name == window for e in evs)]
    if not thread:
        raise ValueError(f"no host span {window!r} in the trace")
    span = next(e for e in thread[0] if e.name == window)
    lo, hi = span.start, span.end
    per_dev: dict[str, list] = {}
    for (plane, _), evs in events(profile, device).items():
        per_dev.setdefault(plane, []).extend(evs)
    if not per_dev:
        raise ValueError("no device operations in the trace")
    busy, ops = [], {}
    for ev in per_dev.values():
        busy.append(busy_seconds(ev, lo, hi))
        for k, (s, n) in op_seconds(ev, lo, hi).items():
            acc = ops.setdefault(k, (0.0, 0))
            ops[k] = (acc[0] + s, acc[1] + n)
    first = next(iter(per_dev.values()))
    idle = gaps(union(((o.start, o.end) for o in first), lo, hi), lo, hi)
    # what the benchmark's own thread was doing: its spans and the calls
    # into JAX that it made
    inner = [h for h in thread[0] if h is not span]
    labelled = sorted(((label(g, inner), (g[1] - g[0]) * 1e-9)
                       for g in idle), key=lambda t: -t[1])
    return Summary(window_s=(hi - lo) * 1e-9,
                   busy_s=sum(busy) / len(busy), ops=ops, idle=labelled)
