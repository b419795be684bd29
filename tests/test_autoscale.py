"""Signal-driven replica autoscaling (day-2 operations, ROADMAP item 1).

Two layers, mirroring tests/test_topology.py's split:

  * CONTROL LOOP — ``Autoscaler`` decisions on synthetic
    ``TopologyReport``-shaped signals against a FakeTopo seam: scale-up
    on credit saturation / shed / per-tenant p99 breach (attributed to
    the HOTTEST group, by scatter heat or served queries), scale-down
    only after ``down_patience`` consecutive idle reports, streak resets
    (hysteresis — no flapping on boundary-riding signals), clamping at
    min/max.

  * LIVE TOPOLOGY — ``ServingTopology.scale_replicas`` structural
    contracts on deterministic FakeShardEngines (duplicated from
    test_topology.py; tests are not a package), and the wired loop:
    a burst stream saturates the FIFO credits -> the autoscaler grows
    the tier; trailing idle streams shrink it back — results stay
    bit-correct across every resize.
"""

import time
import types

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.autoscale import AutoscalePolicy, Autoscaler, ScaleAction
from repro.core.topology import ServingTopology


# ---------------------------------------------------------------------------
# synthetic-signal seam
# ---------------------------------------------------------------------------

class FakeTopo:
    """Just enough ServingTopology surface for the control loop: groups,
    fifo_depth, the cluster partition, and a recording scale_replicas."""

    def __init__(self, n_groups=2, replicas=1, fifo_depth=4, part_of=None):
        self.groups = [[object() for _ in range(replicas)]
                       for _ in range(n_groups)]
        self.fifo_depth = fifo_depth
        self.part_of = part_of
        self.calls = []

    def scale_replicas(self, group, n):
        self.calls.append((group, n))
        g = self.groups[group]
        while len(g) < n:
            g.append(object())
        while len(g) > n:
            g.pop()
        return len(g)


def _report(occ=(0.0, 0.0), shed=0.0, p99=1.0, tenants=None,
            cluster_hits=None, queries=None, depth=4):
    """A TopologyReport-shaped namespace; occ maps to max_in_flight
    against ``depth`` (must match the FakeTopo's fifo_depth)."""
    per_engine = [{"shard": g, "replica": 0,
                   "max_in_flight": int(round(o * depth)),
                   "queries": queries[g] if queries is not None else 32}
                  for g, o in enumerate(occ)]
    return types.SimpleNamespace(
        per_engine=per_engine, shed_fraction=shed, p99_ms=p99,
        tenants=tenants or {}, cluster_hits=cluster_hits)


def test_policy_validation():
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError, match="shed_high"):
        AutoscalePolicy(shed_high=1.0)
    with pytest.raises(ValueError, match="p99_high_ms"):
        AutoscalePolicy(p99_high_ms=0.0)
    with pytest.raises(ValueError, match="occupancy_high"):
        AutoscalePolicy(occupancy_high=0.0)
    with pytest.raises(ValueError, match="occupancy_low"):
        AutoscalePolicy(occupancy_low=0.9, occupancy_high=0.9)
    with pytest.raises(ValueError, match="patience"):
        AutoscalePolicy(up_patience=0)
    with pytest.raises(ValueError, match="step"):
        AutoscalePolicy(step=0)
    with pytest.raises(TypeError, match="AutoscalePolicy"):
        Autoscaler(FakeTopo(), policy={"max_replicas": 4})


def test_scale_up_on_occupancy_after_patience():
    topo = FakeTopo()
    auto = Autoscaler(topo, AutoscalePolicy(up_patience=2, max_replicas=3))
    hot = _report(occ=(1.0, 0.5))
    assert auto.step(hot) == []                    # streak 1 < patience
    acts = auto.step(hot)                          # streak 2: fire
    assert [(a.group, a.direction, a.n_before, a.n_after)
            for a in acts] == [(0, "up", 1, 2)]
    assert len(topo.groups[0]) == 2 and len(topo.groups[1]) == 1
    assert auto.actions == acts                    # kept for the ops log
    assert isinstance(acts[0], ScaleAction) and "occupancy" in acts[0].reason


def test_shed_attributed_to_hottest_group_by_heat():
    """Tier-global shed scales the group carrying the scatter heat, not
    the whole fleet — and a shedding tier is never 'idle' anywhere."""
    part_of = np.array([0, 0, 1, 1])
    topo = FakeTopo(part_of=part_of)
    auto = Autoscaler(topo, AutoscalePolicy(down_patience=1))
    rep = _report(occ=(0.1, 0.1), shed=0.2,
                  cluster_hits=np.array([1.0, 1.0, 40.0, 40.0]))
    sig = auto.observe(rep)
    assert sig[1]["hottest"] and not sig[0]["hottest"]
    assert sig[1]["heat"] == pytest.approx(80 / 82)
    acts = auto.step(rep)
    assert [(a.group, a.direction) for a in acts] == [(1, "up")]
    assert len(topo.groups[0]) == 1                # cold group untouched:
    assert topo.calls == [(1, 2)]                  # not even a down at
    assert all(not s["idle"] for s in sig)         # down_patience=1


def test_heat_falls_back_to_served_queries():
    topo = FakeTopo(part_of=None)                  # no cluster partition
    auto = Autoscaler(topo, AutoscalePolicy())
    rep = _report(occ=(0.1, 0.1), shed=0.5, queries=(100, 1))
    sig = auto.observe(rep)
    assert sig[0]["hottest"] and sig[0]["hot"] and not sig[1]["hot"]
    assert [(a.group, a.direction) for a in auto.step(rep)] == [(0, "up")]


def test_p99_trigger_uses_worst_admitted_tenant():
    """The latency trigger reads the WORST per-tenant p99 (a starved
    tenant must not hide inside the global percentile) and ignores
    tenants that had nothing admitted."""
    topo = FakeTopo()
    pol = AutoscalePolicy(p99_high_ms=100.0)
    auto = Autoscaler(topo, pol)
    ok = _report(p99=500.0, tenants={                # global p99 ignored:
        "a": {"p99_ms": 50.0, "n_admitted": 10},     # admitted tenants fine
        "b": {"p99_ms": 9000.0, "n_admitted": 0}})   # starved-empty: skip
    assert auto.step(ok) == []
    breach = _report(tenants={"a": {"p99_ms": 250.0, "n_admitted": 10}})
    assert [a.direction for a in auto.step(breach)] == ["up"]
    # without tenants the global p99 drives the trigger
    auto2 = Autoscaler(FakeTopo(), pol)
    assert [a.direction for a in auto2.step(_report(p99=250.0))] == ["up"]
    assert auto2.step(_report(p99=50.0)) == []


def test_scale_down_needs_patience_and_clamps_at_min():
    topo = FakeTopo(replicas=2)
    auto = Autoscaler(topo, AutoscalePolicy(down_patience=3))
    idle = _report(occ=(0.0, 0.0))
    assert auto.step(idle) == [] and auto.step(idle) == []
    acts = auto.step(idle)                         # 3rd idle report: fire
    assert [(a.group, a.direction, a.n_after) for a in acts] == \
        [(0, "down", 1), (1, "down", 1)]
    for _ in range(4):                             # at min: never below
        assert auto.step(idle) == []
    assert [len(g) for g in topo.groups] == [1, 1]


def test_clamps_at_max_replicas():
    topo = FakeTopo(replicas=2)
    auto = Autoscaler(topo, AutoscalePolicy(max_replicas=2))
    for _ in range(3):
        assert auto.step(_report(occ=(1.0, 1.0))) == []
    assert [len(g) for g in topo.groups] == [2, 2] and topo.calls == []


def test_hysteresis_streaks_reset_no_flapping():
    topo = FakeTopo(replicas=2)
    auto = Autoscaler(topo, AutoscalePolicy(down_patience=3,
                                            occupancy_low=0.25,
                                            occupancy_high=0.9))
    idle = _report(occ=(0.0, 0.0))
    mid = _report(occ=(0.5, 0.5))                  # neither hot nor idle
    for rep in [idle, idle, mid, idle, idle, mid, idle]:
        assert auto.step(rep) == []                # mid resets the streak
    assert [len(g) for g in topo.groups] == [2, 2]
    # after an action the streaks restart: a fresh window must accumulate
    up = Autoscaler(topo, AutoscalePolicy(up_patience=2, max_replicas=4))
    hot = _report(occ=(1.0, 0.5))                  # group 1 mid: no streaks
    assert up.step(hot) == []
    assert len(up.step(hot)) == 1                  # 1 -> fires at streak 2
    assert up.step(hot) == []                      # reset: streak 1 again
    assert len(up.step(hot)) == 1
    assert [len(g) for g in topo.groups] == [4, 2]


# ---------------------------------------------------------------------------
# live-topology layer: deterministic fake shard engines
# (duplicated from tests/test_topology.py — tests are not a package)
# ---------------------------------------------------------------------------

class _LazyArray:
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
    def __init__(self, n_clusters, k=3, nprobe=2, service_s=0.02,
                 mode="fake", vectors=None):
        self.scfg = types.SimpleNamespace(k=k, nprobe=nprobe, mode=mode)
        self.index = types.SimpleNamespace(n_clusters=n_clusters)
        self.host = types.SimpleNamespace(vectors=vectors)
        self.buckets = ()
        self.service_s = service_s
        self.t_free = 0.0

    @property
    def compile_count(self):
        return 0

    def search_probed(self, q, probes, *, pad_to=None, trim=True):
        q = np.asarray(q)
        t_done = max(time.perf_counter(), self.t_free) + self.service_s
        self.t_free = t_done
        ids = np.repeat(q[:, :1].astype(np.int32), self.scfg.k, axis=1)
        dists = np.zeros((len(q), self.scfg.k), np.float32)
        return types.SimpleNamespace(ids=_LazyArray(ids, t_done),
                                     dists=_LazyArray(dists, t_done)), None


def _fake_sharded(n_shards=2, replicas=1, service_s=0.02, n_queries=64,
                  **kw):
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
    q[:, 0] = np.arange(n)          # column 0 encodes the query index
    return q


def test_scale_replicas_structural():
    topo, groups = _fake_sharded(n_shards=2, replicas=1)
    leader = groups[0][0]
    assert topo.scale_replicas(0, 3) == 3
    assert len(topo.groups[0]) == 3 and len(topo.groups[1]) == 1
    # new replicas are copy views of the leader: same engine state objects
    assert all(e.index is leader.index for e in topo.groups[0])
    assert topo.scale_replicas(0, 1) == 1
    assert topo.groups[0] == [leader]              # shrink pops the copies
    with pytest.raises(ValueError, match="group"):
        topo.scale_replicas(5, 2)
    with pytest.raises(ValueError, match="replica"):
        topo.scale_replicas(0, 0)


def test_results_stay_correct_across_resizes():
    """Scaling between runs never corrupts reassembly: every admitted
    query still gets its own id back whatever the replica counts."""
    n = 24
    q = _indexed_queries(n)
    topo, _ = _fake_sharded(n_shards=2, replicas=1, service_s=1e-4,
                            n_queries=n)
    for sizes in [(2, 1), (3, 2), (1, 1)]:
        for g, s in enumerate(sizes):
            topo.scale_replicas(g, s)
        rep = topo.run(q)
        assert rep.replicas == list(sizes)
        np.testing.assert_array_equal(
            rep.ids[:, 0], np.arange(n, dtype=np.int32))


def test_autoscaler_wired_through_live_topology():
    """The loop end-to-end on fakes: a burst saturates the FIFO credits
    -> scale up; idle trickles -> scale back down; ids stay correct."""
    n, depth = 16, 2
    q = _indexed_queries(n)
    policy = AutoscalePolicy(min_replicas=1, max_replicas=2,
                             occupancy_high=0.9, occupancy_low=0.5,
                             up_patience=1, down_patience=2)
    topo, _ = _fake_sharded(n_shards=2, replicas=1, service_s=5e-3,
                            n_queries=n, fifo_depth=depth, max_batch=4,
                            autoscale=policy)
    assert isinstance(topo.autoscaler, Autoscaler)
    rep = topo.run(q, np.zeros(n))                 # burst: all arrive at 0
    assert max(pe["max_in_flight"] for pe in rep.per_engine) == depth
    ups = topo.autoscaler.step(rep)
    assert {a.direction for a in ups} == {"up"}
    assert [len(g) for g in topo.groups] == [2, 2]
    for _ in range(policy.down_patience):          # idle trickle: one query
        arr = np.arange(n) * (6 * 5e-3)            # in flight at a time
        rep = topo.run(q, arr)
        np.testing.assert_array_equal(
            rep.ids[:, 0], np.arange(n, dtype=np.int32))
        topo.autoscaler.step(rep)
    assert [len(g) for g in topo.groups] == [1, 1]
    downs = [a for a in topo.autoscaler.actions if a.direction == "down"]
    assert len(downs) == 2


def test_serving_topology_rejects_bad_autoscale():
    with pytest.raises((TypeError, ValueError), match="AutoscalePolicy"):
        _fake_sharded(autoscale="on")
