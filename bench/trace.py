"""Profiler window and the reduction from a device trace to numbers.

``WindowTracer`` starts JAX's profiler from the ``ServingTopology.run``
ticker once the stream clock reaches ``start_s`` and stops it at
``stop_s``; a ``TraceAnnotation`` named ``bench_window`` marks the traced
span on the same clock as the device's events. ``load_events`` reads the
``.xplane.pb`` into plain ``Event`` tuples (the device's ``XLA Modules``
and ``XLA Ops`` lines, and the annotation), which is all the reducers
below take: they are checked on a trace recorded on the chip
(``fixtures/``). The op events carry no name scope (their names are HLO
instruction names), so no reducer here can split a step by source layer.

Busy time is the union of the intervals of the device's ``XLA Ops``
events inside the window; idle is the rest of the window.
"""

from __future__ import annotations

import gzip
import json
import math
import re
import shutil
import tempfile
from pathlib import Path
from typing import Iterable, NamedTuple

__all__ = ["Event", "WindowTracer", "load_events", "save_events",
           "read_events", "window_ns", "device_ops", "busy_ns",
           "modules", "top_ops", "idle_gaps"]

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class WindowTracer:
    """A ``run(ticker=...)`` hook that traces stream-clock seconds
    [start_s, stop_s) into a temporary directory. The profiler runs on for
    ``grace_s`` past the window, so that the ops in flight at its end are
    recorded whole (an op is recorded when it ends)."""

    def __init__(self, start_s: float, stop_s: float, grace_s: float = 2.0):
        self.start_s, self.stop_s, self.grace_s = start_s, stop_s, grace_s
        self.dir = None
        self._ann = None
        self._running = False

    def __call__(self, t: float) -> None:
        import jax
        if self.dir is None and t >= self.start_s:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._running = True
            self._ann = jax.profiler.TraceAnnotation(WINDOW)
            self._ann.__enter__()
        elif self._ann is not None and t >= self.stop_s:
            self._end_window()
        elif self._running and t >= self.stop_s + self.grace_s:
            self.close()

    def _end_window(self) -> None:
        self._ann.__exit__(None, None, None)
        self._ann = None

    def close(self) -> None:
        """End the window and the trace, where they still run."""
        import jax
        if self._ann is not None:
            self._end_window()
        if self._running:
            jax.profiler.stop_trace()
            self._running = False

    def events(self) -> list[Event]:
        if self.dir is None:
            return []
        try:
            return load_events(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:")


def short_name(name: str) -> str:
    """An op event is named by its whole HLO instruction
    (``%fusion.199 = pred[...] fusion(...), kind=...``): keep the
    instruction's own name (``fusion.199``), and for a custom call its
    target and any kernel name (``custom-call.3 tpu_custom_call k``)."""
    head = name.split(" = ", 1)[0].lstrip("%")
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target is None:
        return head
    kernel = re.search(r'kernel_name\W+(\w+)', name)
    return " ".join([head, target.group(1)]
                    + ([kernel.group(1)] if kernel else []))


def load_events(trace_dir) -> list[Event]:
    """Events of the device planes' module and op lines, and the window
    annotation, of one trace."""
    from jax.profiler import ProfileData
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return []
    out = []
    for plane in ProfileData.from_file(str(paths[-1])).planes:
        dev = _is_device(plane.name)
        for line in plane.lines:
            keep = dev and line.name in (OPS_LINE, MODULES_LINE)
            for e in line.events:
                if keep or e.name == WINDOW:
                    out.append(Event(plane.name, line.name,
                                     short_name(e.name), float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def save_events(events: Iterable[Event], path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([list(e) for e in events], f)


def read_events(path) -> list[Event]:
    with gzip.open(path, "rt") as f:
        return [Event(*e) for e in json.load(f)]


def window_ns(events: list[Event]) -> tuple[float, float] | None:
    """(start, end) of the ``bench_window`` annotation."""
    for e in events:
        if e.name == WINDOW:
            return e.start_ns, e.end_ns
    return None


def device_ops(events: list[Event]) -> dict[str, list[Event]]:
    """Op events of each device plane, by plane, in start order."""
    out: dict[str, list[Event]] = {}
    for e in events:
        if _is_device(e.plane) and e.line == OPS_LINE:
            out.setdefault(e.plane, []).append(e)
    for v in out.values():
        v.sort(key=lambda e: e.start_ns)
    return out


def _merged(intervals: Iterable[tuple[float, float]]):
    cur = None
    for a, b in sorted(intervals):
        if cur is None or a > cur[1]:
            if cur is not None:
                yield cur
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    if cur is not None:
        yield cur


def busy_ns(ops: list[Event], lo: float, hi: float) -> float:
    """Length of the union of the ops' intervals, clipped to [lo, hi]."""
    return sum(b - a for a, b in _merged(
        (max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops
        if e.end_ns > lo and e.start_ns < hi))


def modules(events: list[Event], pattern: str, lo: float = -math.inf,
            hi: float = math.inf) -> list[Event]:
    """Module executions on a device whose name matches ``pattern`` and
    that lie wholly inside [lo, hi] (an execution the trace cut is left
    out)."""
    rx = re.compile(pattern)
    return sorted((e for e in events if _is_device(e.plane)
                   and e.line == MODULES_LINE and rx.search(e.name)
                   and e.start_ns >= lo and e.end_ns <= hi),
                  key=lambda e: e.start_ns)


def top_ops(ops: list[Event], n: int = 10) -> list[list]:
    """[[op name, seconds], ...] of the ops that took most device time."""
    tot: dict[str, float] = {}
    for e in ops:
        tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns
    return [[k, v * 1e-9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops: list[Event], lo: float, hi: float, n: int = 10
              ) -> list[list]:
    """[[label, seconds], ...] of the longest idle gaps inside [lo, hi],
    labelled by the op that ends where the gap starts. What the host did in
    a gap is not measured: the program has no spans yet."""
    gaps = []
    prev_end, prev_name = lo, "window start"
    names = {}
    for e in ops:
        names.setdefault(e.end_ns, e.name)
    for a, b in _merged((max(e.start_ns, lo), min(e.end_ns, hi)) for e in ops
                        if e.end_ns > lo and e.start_ns < hi):
        if a > prev_end:
            gaps.append((a - prev_end, prev_name))
        prev_end, prev_name = b, names.get(b, "op")
    if hi > prev_end:
        gaps.append((hi - prev_end, prev_name))
    gaps.sort(key=lambda g: -g[0])
    return [[f"after {name}; host activity not measured", g * 1e-9]
            for g, name in gaps[:n]]
