"""Composable serving topology (ISSUE 5 tentpole): hybrid shards x
replicas parity, tier-wide admission control, and the extracted
AdmissionController.

The parity contract: ``topology(shards=N, replicas=R).run(stream)``
admitted results are bit-identical to a single engine searching the same
probed clusters — pinned for N in {2, 4} x R in {1, 2} on batch and
Poisson streams. Timing-sensitive overload mechanisms (deadline shedding,
bounded admission, backpressure) are driven through deterministic
FakeShardEngine doubles, mirroring tests/test_fleet.py's pattern; the
facades' own suites (test_fleet.py / test_sharded.py) run unmodified and
pin the pre-refactor behavior."""

import time
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import compact_index, engine
from repro.core.fleet import FleetScheduler, ShardedFleet
from repro.core.topology import (AdmissionController, ServingTopology,
                                 TopologyReport, partition_index,
                                 replicate_engine, topology)
from repro.data.synthetic import clustered_vectors, query_set


@pytest.fixture(scope="module")
def eng_q():
    x, _ = clustered_vectors(3, 2000, 32, 8)
    q = query_set(3, x, 37)
    icfg = compact_index.IndexConfig(dim=32, n_clusters=8, degree=8, knn_k=16)
    scfg = engine.SearchConfig(nprobe=2, ef=16, k=5)
    eng = engine.PIMCQGEngine.build(jax.random.PRNGKey(0), x, icfg, scfg,
                                    n_shards=2)
    return eng, q


# ---------------------------------------------------------------------------
# hybrid parity: shards x replicas bit-identical to a single engine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards,replicas",
                         [(2, 1), (2, 2), (4, 1), (4, 2)])
def test_hybrid_topology_bit_identical(eng_q, shards, replicas):
    eng, q = eng_q
    sync, _ = eng.search(q)
    topo = topology(eng, shards=shards, replicas=replicas, buckets=(8, 16),
                    fill_threshold=16, wait_limit_s=1e-3, fifo_depth=2)
    rep = topo.run(q)
    assert isinstance(rep, TopologyReport)
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    np.testing.assert_allclose(rep.dists, np.asarray(sync.dists),
                               rtol=1e-5, atol=1e-4)
    assert rep.n_shed == 0 and rep.n_unrouted == 0
    assert np.isfinite(rep.latency_s).all()
    assert rep.shards == shards and rep.replicas == [replicas] * shards
    # the index is partitioned, not replicated: every worker of shard o
    # reports the shard's slice size
    for d in rep.per_engine:
        assert d["clusters"] == 8 // shards
    # every scattered sub-query landed on exactly one worker
    scattered = sum(d["queries"] for d in rep.per_engine)
    assert scattered == round(rep.fanout_mean * len(q))
    assert 1.0 <= rep.fanout_mean <= eng.scfg.nprobe
    if replicas > 1:
        # replication genuinely shares load inside at least one shard
        per_shard = {o: [d["queries"] for d in rep.per_engine
                         if d["shard"] == o] for o in range(shards)}
        assert any(min(v) > 0 for v in per_shard.values())


def test_hybrid_topology_poisson_stream(eng_q):
    eng, q = eng_q
    sync, _ = eng.search(q)
    rng = np.random.default_rng(2)
    arr = np.cumsum(rng.exponential(3e-4, len(q)))
    topo = topology(eng, shards=2, replicas=2, buckets=(4, 8, 16),
                    fill_threshold=16, wait_limit_s=1e-3, fifo_depth=3)
    rep = topo.run(q, arr)
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    assert rep.n_merges >= 2
    assert (rep.latency_s >= 0).all()
    assert rep.p99_ms >= rep.p50_ms
    assert sum(rep.merge_sizes) == len(q)


def test_replicated_topology_matches_single_engine(eng_q):
    """shards=1 is the pure replica tier (the FleetScheduler shape) built
    through the same front door."""
    eng, q = eng_q
    sync, _ = eng.search(q)
    rep = topology(eng, shards=1, replicas=3, buckets=(8, 16),
                   fill_threshold=16, wait_limit_s=1e-3, fifo_depth=2).run(q)
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    assert rep.shards == 1 and rep.n_merges == 0
    assert rep.fanout_mean == 1.0
    assert sum(d["queries"] for d in rep.per_engine) == len(q)


def test_topology_replicas_share_slice_and_cache(eng_q):
    eng, _ = eng_q
    topo = topology(eng, shards=2, replicas=2, buckets=(16,))
    for grp in topo.groups:
        assert len(grp) == 2
        assert grp[1].placed is grp[0].placed          # one device copy
        assert grp[1]._search_cache is grp[0]._search_cache
    # partitions stay disjoint across groups
    seen = []
    for grp in topo.groups:
        seen.extend(np.asarray(grp[0].index.node_ids).ravel().tolist())
    seen = [s for s in seen if s >= 0]
    assert len(seen) == len(set(seen))


def test_topology_warm_precompiles_every_bucket(eng_q):
    eng, q = eng_q
    topo = topology(eng, shards=2, replicas=2, buckets=(8, 16),
                    fill_threshold=16, wait_limit_s=1e-3)
    built = topo.warm()
    assert built == 2 * 2          # 2 shards (replicas share) x 2 buckets
    assert topo.warm() == 0        # idempotent
    before = [g[0].compile_count for g in topo.groups]
    topo.run(q)                    # a real stream adds no executables
    assert [g[0].compile_count for g in topo.groups] == before


def test_heterogeneous_hybrid_routes_by_backend(eng_q):
    """Per-shard backends survive replication: a query requesting a backend
    reaches only the matching shard's replicas."""
    eng, q = eng_q
    topo = topology(eng, shards=2, replicas=2, modes=["mulfree", "exact"],
                    buckets=(8, 16, 64), fill_threshold=64, wait_limit_s=1e-3)
    rep = topo.run(q, backend="exact")
    assert rep.backends == ["mulfree", "exact"]
    assert all(d["queries"] == 0 for d in rep.per_engine if d["shard"] == 0)
    exact_nodes = set(np.asarray(
        topo.groups[1][0].index.node_ids).ravel().tolist()) - {-1}
    got = set(rep.ids[rep.ids >= 0].ravel().tolist())
    assert got and got <= exact_nodes


# ---------------------------------------------------------------------------
# construction validation
# ---------------------------------------------------------------------------

def test_topology_builder_validation(eng_q):
    eng, q = eng_q
    with pytest.raises(ValueError, match="at least one replica"):
        topology(eng, shards=2, replicas=0)
    with pytest.raises(ValueError, match="at least one shard"):
        topology(eng, shards=0)
    with pytest.raises(ValueError, match="shards >= 2"):
        topology(eng, shards=1, modes=["exact"])
    with pytest.raises(ValueError, match="at least one partition"):
        partition_index(eng, 0)
    topo = topology(eng, shards=1, replicas=2, buckets=(16,))
    with pytest.raises(ValueError, match="sharded topology"):
        topo.run(q[:4], backend="exact")


def test_serving_topology_validation(eng_q):
    eng, _ = eng_q
    with pytest.raises(ValueError, match="at least one engine"):
        ServingTopology([])
    with pytest.raises(ValueError, match="at least one engine"):
        ServingTopology([[eng], []], part_of=np.zeros(8), local_cid=np.zeros(8),
                        centroids=np.zeros((8, 32)))
    with pytest.raises(ValueError, match="route"):
        ServingTopology([[eng]], route="random")
    with pytest.raises(ValueError, match="cluster partition"):
        ServingTopology([[eng], [eng]])     # 2 groups, no part_of
    with pytest.raises(ValueError, match="needs part_of"):
        ServingTopology([[eng]], part_of=np.zeros(8, np.int32))


# ---------------------------------------------------------------------------
# AdmissionController unit (the extracted FleetScheduler machinery)
# ---------------------------------------------------------------------------

def test_admission_controller_bounds_and_deadlines():
    arr = np.array([0.0, 0.1, 0.2, 5.0])
    adm = AdmissionController(depth=2, deadline_s=0.5, arrivals=arr)
    assert adm.offer(0) and adm.offer(1)
    assert not adm.offer(2)                    # full queue sheds on arrival
    assert len(adm) == 2
    assert adm.next_deadline() == pytest.approx(0.5)   # head arrived at 0.0
    assert adm.expire(0.4) == []               # nobody past deadline yet
    assert adm.expire(0.55) == [0]             # head expired, next head not
    assert adm.next_deadline() == pytest.approx(0.6)
    assert adm.expire(10.0) == [1]
    assert adm.next_deadline() == np.inf       # empty queue: nothing to shed
    lax = AdmissionController(depth=None, deadline_s=None, arrivals=arr)
    for i in range(4):
        assert lax.offer(i)                    # unbounded, never expires
    assert lax.expire(100.0) == [] and lax.next_deadline() == np.inf


# ---------------------------------------------------------------------------
# deterministic overload behavior on SHARDED topologies (fake engines) —
# the machinery the pre-refactor sharded tier did not have at all
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


class FakeShardEngine:
    """Serial 'device' owning one fake partition. search_probed returns
    ids[i] = int(q[i, 0]) (tests encode the query index in column 0), so
    scatter/gather reassembly across shards, replicas, and the origin
    merge is checkable without real search."""

    def __init__(self, n_clusters, k=3, nprobe=2, service_s=0.02,
                 mode="fake", vectors=None):
        self.scfg = types.SimpleNamespace(k=k, nprobe=nprobe, mode=mode)
        self.index = types.SimpleNamespace(n_clusters=n_clusters)
        self.host = types.SimpleNamespace(vectors=vectors)
        self.buckets = ()
        self.service_s = service_s
        self.t_free = 0.0
        self.outstanding = 0
        self.max_outstanding = 0

    @property
    def compile_count(self):
        return 0

    def search_probed(self, q, probes, *, pad_to=None, trim=True):
        q = np.asarray(q)
        now = time.perf_counter()
        t_done = max(now, self.t_free) + self.service_s
        self.t_free = t_done
        self.outstanding += 1
        self.max_outstanding = max(self.max_outstanding, self.outstanding)
        ids = np.repeat(q[:, :1].astype(np.int32), self.scfg.k, axis=1)
        dists = np.zeros((len(q), self.scfg.k), np.float32)

        def done():
            self.outstanding -= 1

        return types.SimpleNamespace(ids=_LazyArray(ids, t_done, done),
                                     dists=_LazyArray(dists, t_done)), None


def _fake_sharded(n_shards=2, replicas=1, service_s=0.02, n_queries=64,
                  **kw):
    """A sharded ServingTopology over FakeShardEngines: 8 fake clusters
    partitioned contiguously, real cluster_filter routing over separated
    centroids, real merge rerank over a zero vector table (so the fake's
    candidate id — the query index — always survives the origin merge)."""
    C, dim = 8, 4
    per = C // n_shards
    part_of = np.repeat(np.arange(n_shards), per).astype(np.int32)
    local_cid = np.tile(np.arange(per), n_shards).astype(np.int32)
    rng = np.random.default_rng(7)
    centroids = rng.normal(0, 5.0, (C, dim)).astype(np.float32)
    vectors = jnp.zeros((n_queries, dim), jnp.float32)
    groups = [[FakeShardEngine(per, service_s=service_s, vectors=vectors)
               for _ in range(replicas)] for _ in range(n_shards)]
    topo = ServingTopology(groups, part_of=part_of, local_cid=local_cid,
                           centroids=centroids, **kw)
    return topo, groups


def _indexed_queries(n, dim=4):
    rng = np.random.default_rng(11)
    q = rng.normal(0, 5.0, (n, dim)).astype(np.float32)
    q[:, 0] = np.arange(n)      # column 0 encodes the query index
    return q


def test_sharded_topology_sheds_only_past_deadline():
    """Overload a slow sharded tier: queries that could not be dealt within
    shed_deadline_s are dropped BEFORE scattering, and only those — the
    overload machinery the legacy ShardedFleet lacked entirely."""
    n, deadline = 40, 0.05
    q = _indexed_queries(n)

    def build(dl):
        topo, _ = _fake_sharded(2, service_s=0.03, n_queries=n,
                                buckets=(4,), fill_threshold=4,
                                wait_limit_s=1e-3, fifo_depth=1,
                                admission_depth=10_000, shed_deadline_s=dl)
        return topo

    rep = build(deadline).run(q)
    assert rep.n_shed > 0
    assert rep.n_admitted + rep.n_shed == n
    assert (rep.shed_wait_s[rep.shed] >= deadline).all()
    assert np.isnan(rep.shed_wait_s[~rep.shed]).all()
    # shed rows never scattered; admitted rows gathered and merged exactly
    assert (rep.ids[rep.shed] == -1).all()
    assert np.isnan(rep.latency_s[rep.shed]).all()
    adm = ~rep.shed
    assert np.isfinite(rep.latency_s[adm]).all()
    np.testing.assert_array_equal(rep.ids[adm][:, 0], np.nonzero(adm)[0])
    # the same load under a generous deadline sheds nothing
    relaxed = build(10.0).run(q)
    assert relaxed.n_shed == 0 and np.isfinite(relaxed.latency_s).all()


def test_sharded_topology_admission_queue_is_bounded():
    n = 30
    topo, _ = _fake_sharded(2, service_s=0.05, n_queries=n, buckets=(2,),
                            fill_threshold=2, wait_limit_s=1e-3,
                            fifo_depth=1, admission_depth=4,
                            shed_deadline_s=5.0)
    rep = topo.run(_indexed_queries(n))
    # burst at t=0: per-worker credit (1 FIFO slot x 2/bucket) absorbs a
    # few, 4 wait in the queue, the rest shed on arrival
    assert rep.n_shed > 0
    assert rep.n_admitted >= 4
    assert rep.n_shed + rep.n_admitted == n


def test_hybrid_backpressure_bounds_inflight_per_replica():
    """Per-replica in-flight depth never exceeds fifo_depth under a burst —
    the credit check refuses flushes instead of overrunning any device
    FIFO — and every replica of every shard does work."""
    n = 48
    topo, groups = _fake_sharded(2, replicas=2, service_s=0.01,
                                 n_queries=n, buckets=(4,),
                                 fill_threshold=4, wait_limit_s=1e-3,
                                 fifo_depth=2, admission_depth=10_000)
    rep = topo.run(_indexed_queries(n))
    assert rep.n_shed == 0
    for grp in groups:
        for e in grp:
            assert e.max_outstanding <= 2, e.max_outstanding
    assert all(d["queries"] > 0 for d in rep.per_engine)
    np.testing.assert_array_equal(rep.ids[:, 0], np.arange(n))


# ---------------------------------------------------------------------------
# facades stay topology-backed (spot checks; their own suites pin behavior)
# ---------------------------------------------------------------------------

def test_facades_delegate_to_serving_topology(eng_q):
    eng, q = eng_q
    fleet = FleetScheduler(replicate_engine(eng, 2), buckets=(8, 16),
                           fill_threshold=16, wait_limit_s=1e-3)
    assert isinstance(fleet._topo, ServingTopology)
    parts, pl = partition_index(eng, 2)
    sharded = ShardedFleet(parts, pl.shard_of, pl.local_slot,
                           eng.index.centroids, buckets=(8, 16),
                           fill_threshold=16, wait_limit_s=1e-3)
    assert isinstance(sharded._topo, ServingTopology)
    # legacy facade keeps the eager-scatter, no-shedding configuration
    assert sharded._topo.admission_depth is None
    assert sharded._topo.shed_deadline_s is None
    assert not sharded._topo.backpressure
    # and both reproduce the single-engine result (full contract pinned in
    # test_fleet.py / test_sharded.py)
    sync, _ = eng.search(q)
    np.testing.assert_array_equal(fleet.run(q).ids, np.asarray(sync.ids))
    np.testing.assert_array_equal(sharded.run(q).ids, np.asarray(sync.ids))


# ---------------------------------------------------------------------------
# gather stage: variable per-query fanout (the adaptive path's common case)
# ---------------------------------------------------------------------------

def test_finish_partial_variable_fanout():
    """ShardedSink.finish_partial with UNEVEN owner counts: queries whose
    probes touch 1, 2 and 3 shards gather into slot-major runs, count down
    independently, and become ready exactly when their own last shard
    answers — regardless of deposit order."""
    from repro.core.topology import ShardedSink
    k, fanout, n = 3, 3, 4
    sink = ShardedSink(np.zeros((n, 8), np.float32), np.zeros(n), k, fanout)
    sink.pending[:] = [1, 3, 2, 2]

    def runs(shard):        # distinct, recognizable per-(query,shard) runs
        ids = np.arange(k, dtype=np.int32)[None, :]
        return (lambda idxs: (100 * shard + 10 * idxs[:, None] + ids,
                              (shard + 1.0) * np.ones((len(idxs), k),
                                                      np.float32)))

    # shard 0 answers queries {0, 1, 2} at their slot 0
    sink.finish_partial(np.array([0, 1, 2]), np.array([0, 0, 0]),
                        *runs(0)(np.array([0, 1, 2])))
    assert [int(i) for i, _ in sink.ready] == [0]     # fanout-1 query done
    # shard 1 answers {1, 3} (query 3's FIRST slot is shard 1's answer)
    sink.finish_partial(np.array([1, 3]), np.array([1, 0]),
                        *runs(1)(np.array([1, 3])))
    assert [int(i) for i, _ in sink.ready] == [0]
    # shard 2 answers {1, 2, 3} — queries 1 (3rd of 3), 2 (2nd of 2),
    # 3 (2nd of 2) all complete in this deposit
    sink.finish_partial(np.array([1, 2, 3]), np.array([2, 1, 1]),
                        *runs(2)(np.array([1, 2, 3])))
    assert [int(i) for i, _ in sink.ready] == [0, 1, 2, 3]
    assert (sink.pending == 0).all()
    # slot-major layout: query 1 filled slots 0,1,2; query 2 slots 0,1 from
    # shards 0,2; unfilled tails stay (-1, inf)
    np.testing.assert_array_equal(
        sink.part_ids[1], np.concatenate([100 * s + 10 * 1 + np.arange(3)
                                          for s in (0, 1, 2)]))
    np.testing.assert_array_equal(
        sink.part_ids[2][:2 * k],
        np.concatenate([10 * 2 + np.arange(3), 200 + 10 * 2 + np.arange(3)]))
    assert (sink.part_ids[2][2 * k:] == -1).all()
    assert np.isinf(sink.part_d[2][2 * k:]).all()
    assert (sink.part_ids[0][k:] == -1).all()


# ---------------------------------------------------------------------------
# adaptive early termination (SearchConfig.adaptive_*)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def adaptive_eng_q():
    x, _ = clustered_vectors(5, 2000, 32, 8)
    q = query_set(5, x, 37)
    icfg = compact_index.IndexConfig(dim=32, n_clusters=8, degree=8,
                                     knn_k=16)
    scfg = engine.SearchConfig(nprobe=4, ef=16, k=5, adaptive_tau=2.0,
                               adaptive_ladder=(2, 4))
    eng = engine.PIMCQGEngine.build(jax.random.PRNGKey(1), x, icfg, scfg,
                                    n_shards=2)
    return eng, q


def test_adaptive_topology_matches_adaptive_single_engine(adaptive_eng_q):
    """With termination ON, the sharded scatter masks exactly the probes
    the single adaptive engine masks — results stay bit-identical (ids)
    between the fleet and one engine at the same adaptive config."""
    eng, q = adaptive_eng_q
    sync, _ = eng.search(q)
    topo = topology(eng, shards=2, replicas=1, buckets=(8, 16),
                    fill_threshold=16, wait_limit_s=1e-3)
    rep = topo.run(q)
    np.testing.assert_array_equal(rep.ids, np.asarray(sync.ids))
    np.testing.assert_allclose(rep.dists, np.asarray(sync.dists),
                               rtol=1e-5, atol=1e-4)


def test_adaptive_reduces_fanout(adaptive_eng_q):
    """Easy queries keep fewer probes, so the mean shard fanout drops
    strictly below the fixed-effort scatter's."""
    eng, q = adaptive_eng_q
    fixed = engine.PIMCQGEngine.build(
        jax.random.PRNGKey(1),
        np.asarray(eng.host.vectors),
        compact_index.IndexConfig(dim=32, n_clusters=8, degree=8, knn_k=16),
        engine.SearchConfig(nprobe=4, ef=16, k=5), n_shards=2)
    t_fix = topology(fixed, shards=2, replicas=1, buckets=(8, 16),
                     fill_threshold=16, wait_limit_s=1e-3)
    t_ad = topology(eng, shards=2, replicas=1, buckets=(8, 16),
                    fill_threshold=16, wait_limit_s=1e-3)
    assert (t_ad.adaptive_tau, t_ad.adaptive_ladder) == (2.0, (2, 4))
    rep_f, rep_a = t_fix.run(q), t_ad.run(q)
    assert rep_a.fanout_mean < rep_f.fanout_mean
    # at equal effort ladder top == nprobe, results can only differ where
    # probes were dropped; recall parity is gated in benchmarks/qps_recall
    assert rep_a.n_shed == 0 and rep_a.n_unrouted == 0
