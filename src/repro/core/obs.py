"""Spans and counters of the serving path.

The one way the program records what it does while serving:

  * ``span(name, **args)`` — a host span. It is a
    ``jax.profiler.TraceAnnotation``, so while the profiler runs it lands
    in the same trace as the device's op lines, on the same clock, with
    ``args`` kept as event stats; while it does not, entering one costs a
    check of a flag. Spans are opened only where work happens (a flush, a
    harvest, a nap of the run loop), never once per loop iteration.

      ``serve.flush``    take, pad and enqueue one flush (args flush,
                         rows, bucket); inside it ``serve.dispatch``, the
                         execution backend's search call (a compile
                         shows there)
      ``serve.finish``   host copy of a finished flush and its sink
                         write (args flush); inside it ``serve.block``,
                         the wait on the device
      ``serve.nap``      one idle stretch of the run loop: it polls and
                         sleeps until work is due
      ``serve.shed``     a query shed (args query)
      ``serve.route``    origin probe selection and scatter split
                         (sharded tiers)
      ``serve.merge``    origin merge of gathered partial top-k (sharded
                         tiers; args rows)
      ``serve.gc``       one pass of Python's collector (args generation,
                         collected)

  * ``Idle`` — the run loop's sleeps, one ``serve.nap`` span per idle
    stretch.
  * ``Counters`` — plain integers kept per run (one per ``StreamSink``)
    and returned in the run's report: lane slots, live lanes and hops of
    every served flush, and the collector's passes inside the run.
  * ``gc_spans(counters)`` — a ``gc.callbacks`` hook, installed for the
    length of one run, that turns each collection into a ``serve.gc``
    span and counts it.

Device ops are attributed by a ``jax.named_scope`` per stage of the
serving executables: ``cluster_filter``, ``route_lanes``,
``prepare_lanes``, ``beam_search`` (inside its loop body ``visited``,
``expand``, ``rank``, ``select``) and ``rerank``; the sharded origin's
merge runs as ``jit(merge_topk)``. Scopes change op metadata only, never
what is computed.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import jax
import numpy as np

__all__ = ["span", "Idle", "Counters", "gc_spans"]


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span on the profiler's clock (a context manager)."""
    return jax.profiler.TraceAnnotation(name, **args)


class Idle:
    """The ``serve.nap`` span of a run loop's idle stretch: the stretch's
    first sleep opens it and ``wake``, called where work starts, closes
    it, so a stretch of many polls is one span."""

    def __init__(self):
        self._ann = None

    def sleep(self, seconds: float) -> None:
        if self._ann is None:
            self._ann = span("serve.nap")
            self._ann.__enter__()
        time.sleep(seconds)

    def wake(self) -> None:
        if self._ann is not None:
            ann, self._ann = self._ann, None
            ann.__exit__(None, None, None)


@dataclasses.dataclass
class Counters:
    """Per-run totals over the device executions a run harvested.

    ``lane_slots`` sums S x L over flushes, ``live_lanes`` the lanes that
    took at least one hop, ``hops`` all hops, ``slot_hops`` the slots
    times the slowest lane's hops of each execution (what lockstep costs:
    every slot runs as long as the slowest lane), ``dropped_lanes`` the
    lanes lost to lane-buffer overflow (a query answered from fewer probes
    than asked). ``gc_collections``/``gc_s`` count the collector's passes
    and seconds inside the run."""
    flushes: int = 0
    lane_slots: int = 0
    live_lanes: int = 0
    hops: int = 0
    slot_hops: int = 0
    dropped_lanes: int = 0
    gc_collections: int = 0
    gc_s: float = 0.0

    def add_flush(self, stats) -> None:
        """Count one harvested execution; ``stats`` is its
        ``SearchStats`` (hops (..., S, L): leading axes are devices that
        run their loops apart) or None where the backend reports none."""
        self.flushes += 1
        if stats is None:
            return
        hops = np.asarray(stats.hops, np.int64)
        per_dev = hops.reshape(-1, hops.shape[-2] * hops.shape[-1])
        self.lane_slots += int(per_dev.size)
        self.live_lanes += int((per_dev > 0).sum())
        self.hops += int(per_dev.sum())
        self.slot_hops += int((per_dev.shape[1] * per_dev.max(axis=1)).sum())
        self.dropped_lanes += int(np.asarray(stats.dropped_lanes).sum())


@contextlib.contextmanager
def gc_spans(counters: Counters):
    """For the length of the block, each pass of Python's collector is a
    ``serve.gc`` span and is counted in ``counters``."""
    open_: list = []

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            ann = span("serve.gc", generation=info["generation"])
            ann.__enter__()
            open_.append((ann, time.perf_counter()))
        elif open_:
            ann, t0 = open_.pop()
            counters.gc_s += time.perf_counter() - t0
            counters.gc_collections += 1
            ann.set_metadata(collected=info["collected"])
            ann.__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    try:
        yield
    finally:
        gc.callbacks.remove(on_gc)
