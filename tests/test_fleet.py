"""Fleet serving tier (ISSUE 3 tentpole): multi-engine sharding parity,
deadline load shedding, credit-based backpressure, routing policies.

Real-engine tests pin the bit-parity contract (admitted fleet results ==
unpadded single-engine search). Timing-sensitive mechanisms (shedding,
backpressure) are driven through a deterministic FakeEngine test double
whose 'device' is a serial server with a fixed service time."""

import time
import types

import numpy as np
import jax
import pytest

from repro.core import compact_index, engine
from repro.core.fleet import FleetReport, FleetScheduler, replicate_engine
from repro.data.synthetic import clustered_vectors, query_set


# ---------------------------------------------------------------------------
# deterministic engine double
# ---------------------------------------------------------------------------

class _LazyArray:
    """Mimics a jax.Array still in flight: is_ready() flips at t_done and
    np.asarray blocks until then (the worker's harvest contract)."""

    def __init__(self, a, t_done, on_materialize=None):
        self._a = a
        self._t_done = t_done
        self._on_materialize = on_materialize

    def is_ready(self):
        return time.perf_counter() >= self._t_done

    def __array__(self, dtype=None, *_, **__):
        wait = self._t_done - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if self._on_materialize is not None:
            cb, self._on_materialize = self._on_materialize, None
            cb()
        a = self._a
        return a if dtype is None else a.astype(dtype)


class FakeEngine:
    """Serial 'device' with a fixed per-flush service time. Returns
    ids[i] = int(q[i, 0]) (tests encode the query index in column 0), so
    reassembly across engines/flushes is checkable without real search."""

    def __init__(self, k=3, service_s=0.02):
        self.scfg = types.SimpleNamespace(k=k, mode="fake")
        self.buckets = ()
        self.service_s = service_s
        self.t_free = 0.0              # device busy until (perf_counter)
        self.outstanding = 0           # dispatched, not yet harvested
        self.max_outstanding = 0
        self.n_flushes = 0

    @property
    def compile_count(self):
        return 0

    def search(self, q, *, pad_to=None, trim=True):
        q = np.asarray(q)
        now = time.perf_counter()
        t_done = max(now, self.t_free) + self.service_s
        self.t_free = t_done
        self.n_flushes += 1
        self.outstanding += 1
        self.max_outstanding = max(self.max_outstanding, self.outstanding)
        ids = np.repeat(q[:, :1].astype(np.int32), self.scfg.k, axis=1)
        dists = np.zeros((len(q), self.scfg.k), np.float32)

        def done():
            self.outstanding -= 1

        res = types.SimpleNamespace(ids=_LazyArray(ids, t_done, done),
                                    dists=_LazyArray(dists, t_done))
        return res, None


def _indexed_queries(n, dim=4):
    q = np.zeros((n, dim), np.float32)
    q[:, 0] = np.arange(n)
    return q


# ---------------------------------------------------------------------------
# bit-parity with a single engine (real engines)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def eng_q():
    x, _ = clustered_vectors(3, 2000, 32, 8)
    q = query_set(3, x, 37)
    icfg = compact_index.IndexConfig(dim=32, n_clusters=8, degree=8, knn_k=16)
    scfg = engine.SearchConfig(nprobe=2, ef=16, k=5)
    eng = engine.PIMCQGEngine.build(jax.random.PRNGKey(0), x, icfg, scfg,
                                    n_shards=2)
    return eng, q


@pytest.mark.parametrize("route", ["round-robin", "least-in-flight"])
def test_fleet_matches_single_engine_bit_identical(eng_q, route):
    """Non-shed fleet results must be bit-identical (ids) to an unpadded
    single-engine search of the same stream, across both routing policies
    and a fleet of 3 replicas."""
    eng, q = eng_q
    sync, _ = eng.search(q)
    fleet = FleetScheduler(replicate_engine(eng, 3), route=route,
                           buckets=(8, 16), fill_threshold=16,
                           wait_limit_s=1e-3, fifo_depth=2)
    rep = fleet.run(q)
    assert rep.n_shed == 0 and rep.shed_fraction == 0.0
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    np.testing.assert_allclose(rep.dists, np.asarray(sync.dists),
                               rtol=1e-5, atol=1e-4)
    assert np.isfinite(rep.latency_s).all()
    assert sum(d["queries"] for d in rep.per_engine) == len(q)
    # the stream was genuinely sharded: more than one engine did work
    assert sum(1 for d in rep.per_engine if d["queries"] > 0) >= 2


def test_fleet_poisson_stream_reassembles(eng_q):
    eng, q = eng_q
    sync, _ = eng.search(q)
    rng = np.random.default_rng(2)
    arr = np.cumsum(rng.exponential(3e-4, len(q)))
    fleet = FleetScheduler(replicate_engine(eng, 2), buckets=(4, 8, 16),
                           fill_threshold=16, wait_limit_s=1e-3, fifo_depth=3)
    rep = fleet.run(q, arr)
    assert rep.n_shed == 0
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    assert rep.n_flushes >= 2
    assert (rep.latency_s >= 0).all()
    assert rep.p99_ms >= rep.p50_ms


# ---------------------------------------------------------------------------
# deadline load shedding (fake engines, deterministic timing)
# ---------------------------------------------------------------------------

def test_fleet_sheds_only_past_deadline():
    """Overload a single slow engine: queries that could not be dispatched
    within shed_deadline_s are dropped, and ONLY those — every shed query's
    recorded queue wait meets the deadline, every admitted query completes,
    and a generous deadline sheds nothing on the identical offered load."""
    n, deadline = 40, 0.05
    q = _indexed_queries(n)

    def build(dl):
        return FleetScheduler([FakeEngine(service_s=0.03)], buckets=(4,),
                              fill_threshold=4, wait_limit_s=1e-3,
                              fifo_depth=1, admission_depth=10_000,
                              shed_deadline_s=dl)

    rep = build(deadline).run(q)              # 40 at t=0, ~7.5ms/query drain
    assert rep.n_shed > 0
    assert rep.n_admitted + rep.n_shed == n
    # shedding kicked in only past the configured deadline
    assert (rep.shed_wait_s[rep.shed] >= deadline).all()
    assert np.isnan(rep.shed_wait_s[~rep.shed]).all()
    # shed rows never reached the output arrays; admitted rows all did
    assert (rep.ids[rep.shed] == -1).all()
    assert np.isnan(rep.latency_s[rep.shed]).all()
    assert np.isfinite(rep.latency_s[~rep.shed]).all()
    assert (rep.ids[~rep.shed] >= 0).all()
    # the same load under a generous deadline sheds nothing
    relaxed = build(10.0).run(q)
    assert relaxed.n_shed == 0 and np.isfinite(relaxed.latency_s).all()


def test_fleet_admission_queue_is_bounded():
    """Arrivals beyond the admission queue's depth are shed immediately."""
    n = 30
    fleet = FleetScheduler([FakeEngine(service_s=0.05)], buckets=(2,),
                           fill_threshold=2, wait_limit_s=1e-3, fifo_depth=1,
                           admission_depth=4, shed_deadline_s=5.0)
    rep = fleet.run(_indexed_queries(n))
    # burst of 30 at t=0: 1 FIFO slot x 2/bucket buffered + 4 queued admit
    # at most a handful before overflow shedding starts
    assert rep.n_shed >= n - (4 + 2 * 2 + 2)
    assert rep.n_admitted >= 4


def test_fleet_backpressure_bounds_inflight():
    """Per-engine in-flight depth never exceeds fifo_depth — the credit
    check refuses flushes instead of overrunning the device FIFO — and no
    engine stalls its siblings (all engines end up doing work)."""
    engines = [FakeEngine(service_s=0.015), FakeEngine(service_s=0.015)]
    fleet = FleetScheduler(engines, buckets=(4,), fill_threshold=4,
                           wait_limit_s=1e-3, fifo_depth=2,
                           admission_depth=10_000)
    rep = fleet.run(_indexed_queries(48))
    assert rep.n_shed == 0
    for e, stats in zip(engines, rep.per_engine):
        assert e.max_outstanding <= 2, e.max_outstanding
        assert stats["max_in_flight"] <= 2
        assert stats["queries"] > 0                   # both replicas worked
    # reassembly across two engines' interleaved flushes is exact
    np.testing.assert_array_equal(rep.ids[:, 0], np.arange(48))


def test_fleet_round_robin_deals_across_engines():
    engines = [FakeEngine(service_s=0.005) for _ in range(3)]
    fleet = FleetScheduler(engines, route="round-robin", buckets=(4,),
                           fill_threshold=4, wait_limit_s=1e-3, fifo_depth=2,
                           admission_depth=10_000)
    rep = fleet.run(_indexed_queries(48))
    counts = [d["queries"] for d in rep.per_engine]
    assert sum(counts) == 48
    assert min(counts) > 0                            # nobody starved
    np.testing.assert_array_equal(np.sort(rep.ids[:, 0]), np.arange(48))


# ---------------------------------------------------------------------------
# construction validation
# ---------------------------------------------------------------------------

def test_fleet_constructor_validation():
    e = FakeEngine()
    with pytest.raises(ValueError, match="at least one engine"):
        FleetScheduler([])
    with pytest.raises(ValueError, match="route"):
        FleetScheduler([e], route="random")
    with pytest.raises(ValueError, match="shed_deadline_s"):
        FleetScheduler([e], buckets=(4,), shed_deadline_s=0.0)
    with pytest.raises(ValueError, match="admission_depth"):
        FleetScheduler([e], buckets=(4,), admission_depth=0)
    with pytest.raises(ValueError, match="disagree on k"):
        FleetScheduler([FakeEngine(k=3), FakeEngine(k=5)], buckets=(4,))
    with pytest.raises(ValueError):
        replicate_engine(e, 0)


def test_replicate_engine_shares_placed_state(eng_q):
    eng, _ = eng_q
    reps = replicate_engine(eng, 3)
    assert len(reps) == 3 and reps[0] is eng
    assert all(r.placed is eng.placed for r in reps)        # one device copy
    assert all(r._search_cache is eng._search_cache for r in reps)
    fresh = replicate_engine(eng, 2, share_executables=False)
    assert fresh[1]._search_cache is not eng._search_cache


def test_fleet_report_has_goodput_semantics():
    """qps counts admitted queries only; percentiles ignore shed NaNs."""
    fleet = FleetScheduler([FakeEngine(service_s=0.03)], buckets=(4,),
                           fill_threshold=4, wait_limit_s=1e-3, fifo_depth=1,
                           admission_depth=10_000, shed_deadline_s=0.04)
    rep = fleet.run(_indexed_queries(40))
    assert isinstance(rep, FleetReport)
    assert rep.n_shed > 0
    assert rep.qps == pytest.approx(rep.n_admitted / rep.makespan_s)
    assert np.isfinite(rep.p50_ms) and np.isfinite(rep.p99_ms)
    assert rep.shed_fraction == rep.n_shed / rep.n_queries
