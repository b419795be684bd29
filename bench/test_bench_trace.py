"""The reduction from a device trace to numbers, on hand-made events and
on a trace recorded on the chip (``fixtures/sift1m_batch_trace.json.gz``:
a few hundred milliseconds of ``sift1m`` batch serving on one TPU v5e)."""

import numpy as np
import pytest

from bench import run, trace
from bench.conftest import BENCH

DEV = "/device:TPU:0"
FIXTURE = BENCH / "fixtures" / "sift1m_batch_trace.json.gz"


def op(name, start, dur, line=trace.OPS_LINE, plane=DEV):
    return trace.Event(plane, line, name, float(start), float(dur))


def window(lo, hi):
    return trace.Event("/host:CPU", "python3", trace.WINDOW, float(lo),
                       float(hi - lo))


HAND = [window(100, 1100),
        op("jit_search_step(1)", 50, 300, trace.MODULES_LINE),
        op("jit_search_step(1)", 400, 400, trace.MODULES_LINE),
        op("jit_search_step(1)", 900, 400, trace.MODULES_LINE),
        op("while.1", 50, 250), op("fusion.2", 280, 70),   # overlap
        op("while.1", 400, 300), op("sort.3", 750, 50),
        op("while.1", 900, 400)]


def test_busy_is_the_union_clipped_to_the_window():
    ops = trace.device_ops(HAND)[DEV]
    # [100, 350) + [400, 700) + [750, 800) + [900, 1100)
    assert trace.busy_ns(ops, 100, 1100) == 250 + 300 + 50 + 200


def test_idle_share_and_gaps_on_hand_made_events():
    ctx = run.Context(config={}, peaks={}, setup_s=0, window_s=1,
                      answered=None, latency_s=None, good=None, recall=0,
                      flush_sizes=[], buckets=(64,), events=HAND)
    assert run.load_reader("idle_share.batch")(ctx) == pytest.approx(20.0)
    gaps = trace.idle_gaps(trace.device_ops(HAND)[DEV], 100, 1100)
    assert [g[1] for g in gaps] == pytest.approx([100e-9, 50e-9, 50e-9])
    assert gaps[0][0].startswith("after sort.3")


def test_step_takes_only_executions_wholly_inside_the_window():
    calls = trace.modules(HAND, "search_step", 100, 1100)
    assert [c.start_ns for c in calls] == [400.0]
    ctx = run.Context(config={}, peaks={}, setup_s=0, window_s=1,
                      answered=None, latency_s=None, good=None, recall=0,
                      flush_sizes=[], buckets=(64,), events=HAND)
    assert run.load_reader("step_ms.batch")(ctx) == pytest.approx(400e-6)


def test_top_ops_sums_by_name():
    top = trace.top_ops(trace.device_ops(HAND)[DEV], 2)
    assert top == [["while.1", pytest.approx(950e-9)],
                   ["fusion.2", pytest.approx(70e-9)]]


def test_events_round_trip(tmp_path):
    path = tmp_path / "t.json.gz"
    trace.save_events(HAND, path)
    assert trace.read_events(path) == HAND


@pytest.fixture(scope="module")
def chip():
    return trace.read_events(FIXTURE)


def timeline_busy(ops, lo, hi):
    """Busy time counted on a 1 us grid, independently of the reducer."""
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for e in ops:
        a = max(int((e.start_ns - lo) // 1000), 0)
        b = min(int(np.ceil((e.end_ns - lo) / 1000)), len(grid))
        if b > a:
            grid[a:b] = True
    return grid.sum() * 1000.0


def test_chip_trace_busy_matches_a_timeline(chip):
    lo, hi = trace.window_ns(chip)
    ops = trace.device_ops(chip)
    assert list(ops) == [DEV]
    busy = trace.busy_ns(ops[DEV], lo, hi)
    assert 0 < busy <= hi - lo
    assert busy == pytest.approx(timeline_busy(ops[DEV], lo, hi),
                                 rel=5e-3)


def test_chip_trace_names_the_serving_step(chip):
    lo, hi = trace.window_ns(chip)
    calls = trace.modules(chip, "search_step", lo, hi)
    assert calls and all(lo <= c.start_ns and c.end_ns <= hi for c in calls)
    # one flush of 64 on a v5e takes on the order of 0.1-1 s
    assert all(1e8 < c.dur_ns < 1e9 for c in calls)
    top = trace.top_ops(trace.device_ops(chip)[DEV], 3)
    assert top[0][0].startswith("while")      # the beam search's loop


def test_chip_trace_metrics_are_shares_and_times(chip):
    ctx = run.Context(config={}, peaks={}, setup_s=0, window_s=1,
                      answered=None, latency_s=None, good=None, recall=0,
                      flush_sizes=[], buckets=(64,), events=chip)
    idle = run.load_reader("idle_share.batch")(ctx)
    assert 0 <= idle < 100
    step = run.load_reader("step_ms.batch")(ctx)
    assert 100 < step < 1000
