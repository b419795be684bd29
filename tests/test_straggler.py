"""Straggler mitigation (ISSUE 6 satellite): EwmaTracker / DeadlineReissue
unit behavior, deterministic hedged-dispatch tail rescue under the
core.pipeline event simulator, and the real serving topology's hedged
scatter path (speculative re-dispatch to the least-loaded replica, first
response wins, duplicates dropped before deposit) with its
TopologyReport accounting."""

import time
import types

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.pipeline import EventSimulator, LinkModel, StageCosts
from repro.core.topology import ServingTopology
from repro.distributed.straggler import (DeadlineReissue, EwmaTracker,
                                         HedgeConfig)


# ---------------------------------------------------------------------------
# unit behavior
# ---------------------------------------------------------------------------

def test_ewma_converges_to_steady_signal():
    tr = EwmaTracker(alpha=0.2)
    assert tr.value is None
    tr.update(1.0)
    assert tr.value == 1.0                 # first sample adopted exactly
    for _ in range(60):
        tr.update(5.0)
    assert abs(tr.value - 5.0) < 1e-4      # (1-alpha)^60 residual

    # smoothing: one outlier moves the estimate by exactly alpha * delta
    tr2 = EwmaTracker(alpha=0.25, value=2.0)
    tr2.update(10.0)
    assert tr2.value == pytest.approx(2.0 + 0.25 * 8.0)


def test_hedge_config_validation():
    HedgeConfig()                          # defaults valid
    with pytest.raises(ValueError):
        HedgeConfig(k=0.0)
    with pytest.raises(ValueError):
        HedgeConfig(max_reissue=0)
    with pytest.raises(ValueError):
        HedgeConfig(alpha=0.0)
    with pytest.raises(ValueError):
        HedgeConfig(alpha=1.5)


def test_deadline_reissue_poll_and_dedup():
    t = {"now": 0.0}
    dr = DeadlineReissue(k=2.0, max_reissue=1, clock=lambda: t["now"])
    # unseeded tracker: nothing is ever overdue, but next_deadline points
    # at the oldest dispatch so an event loop keeps polling, not blocking
    dr.dispatch("a")
    t["now"] = 100.0
    assert dr.poll() == [] and dr.next_deadline() == 0.0
    assert dr.complete("a")                # seeds EWMA with 100s
    # "b" dispatched at t=100, deadline = 100 + 2*100 = 300
    dr.dispatch("b")
    assert dr.next_deadline() == pytest.approx(300.0)
    t["now"] = 250.0
    assert dr.poll() == []
    t["now"] = 301.0
    assert dr.poll() == ["b"]
    assert dr.reissued_total == 1
    # reissue budget spent: no longer overdue-eligible, deadline is inf
    assert dr.poll() == [] and dr.next_deadline() == np.inf
    assert dr.complete("b")                # first response wins
    assert not dr.complete("b")            # speculative copy: dropped
    assert dr.duplicate_results == 1


# ---------------------------------------------------------------------------
# deterministic tail rescue under the event simulator (the harness that
# lets a wall-clock policy class be asserted exactly)
# ---------------------------------------------------------------------------

def _sim_costs():
    # t_proc-dominant so the search stage (the hedged one) owns the tail
    link = LinkModel(setup_s=1e-6, bw_bytes_s=50e9, knee_bytes=1 << 20)
    return StageCosts(t_pre=lambda n: 1e-6,
                      t_proc=lambda n: 100e-6 * n + 20e-6,
                      t_post=lambda n: 2e-6,
                      link=link, query_bytes=64, result_bytes=64)


def test_hedged_dispatch_rescues_straggler_tail_2x():
    """One PU running 10x slow; its replica group partner absorbs hedged
    re-dispatches. Same queries complete either way; hedged p99 recovers
    >= 2x. Every quantity is closed-form in the simulator — the assertion
    is exact, not a timing race."""
    sim = EventSimulator(n_pus=4, costs=_sim_costs(), rerank_workers=2,
                         fifo_depth=4)
    n, mb = 256, 8
    speed = [10.0, 1.0, 1.0, 1.0]          # PU0 is the straggler
    groups = [[0, 1], [2, 3]]              # replica sets for reissue
    base = sim.pipeline(n, mb, pu_speed=speed)
    dr = DeadlineReissue(k=2.0, max_reissue=1,
                         tracker=EwmaTracker(alpha=0.2))
    hedged = sim.pipeline(n, mb, pu_speed=speed, hedge=dr,
                          hedge_groups=groups)
    assert base.n_queries == hedged.n_queries == n     # equal results
    assert base.n_reissued == 0 and base.n_duplicate_drops == 0
    assert hedged.n_reissued > 0
    assert hedged.n_duplicate_drops == hedged.n_reissued
    assert hedged.p99_latency_s <= base.p99_latency_s / 2.0, \
        (hedged.p99_latency_s, base.p99_latency_s)
    # hedging trades duplicated search work for the tail — never goodput
    assert hedged.qps >= base.qps


def test_hedged_dispatch_is_deterministic():
    sim = EventSimulator(n_pus=4, costs=_sim_costs(), rerank_workers=2)
    runs = []
    for _ in range(2):
        dr = DeadlineReissue(k=2.0, max_reissue=1)
        runs.append(sim.pipeline(128, 8, pu_speed=[10, 1, 1, 1], hedge=dr,
                                 hedge_groups=[[0, 1], [2, 3]]))
    assert runs[0].p99_latency_s == runs[1].p99_latency_s
    assert runs[0].n_reissued == runs[1].n_reissued
    assert runs[0].makespan_s == runs[1].makespan_s


# ---------------------------------------------------------------------------
# the real topology's hedged scatter path (FakeShardEngine doubles — a
# local slim copy of the test_topology scaffolding; tests are not a
# package, so no cross-module import)
# ---------------------------------------------------------------------------

class _Lazy:
    def __init__(self, a, t_done):
        self._a, self._t = a, t_done

    def is_ready(self):
        return time.perf_counter() >= self._t

    def __array__(self, dtype=None, *_, **__):
        wait = self._t - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        return self._a if dtype is None else self._a.astype(dtype)


class _FakeShardEngine:
    """search_probed returns ids[i] = int(q[i, 0]) after service_s of
    simulated device time (serialized per engine), so hedging across
    replicas with very different service times is observable while the
    merged results stay exactly checkable."""

    def __init__(self, n_clusters, k=3, nprobe=2, service_s=0.01,
                 vectors=None):
        self.scfg = types.SimpleNamespace(k=k, nprobe=nprobe, mode="fake")
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
        return types.SimpleNamespace(ids=_Lazy(ids, t_done),
                                     dists=_Lazy(dists, t_done)), None


def _fake_topo(n, *, slow_s=None, hedge=None):
    """2 shards; shard 0 has a SLOW replica (service slow_s) and a fast
    one, shard 1 two fast ones. Round-robin routing guarantees the slow
    replica receives primary flushes."""
    C, dim, n_shards, replicas = 8, 4, 2, 2
    per = C // n_shards
    part_of = np.repeat(np.arange(n_shards), per).astype(np.int32)
    local_cid = np.tile(np.arange(per), n_shards).astype(np.int32)
    rng = np.random.default_rng(7)
    centroids = rng.normal(0, 5.0, (C, dim)).astype(np.float32)
    vectors = jnp.zeros((n, dim), jnp.float32)
    fast = 0.01
    svc = {(0, 0): slow_s if slow_s is not None else fast}
    groups = [[_FakeShardEngine(per, service_s=svc.get((o, r), fast),
                                vectors=vectors)
               for r in range(replicas)] for o in range(n_shards)]
    topo = ServingTopology(groups, part_of=part_of, local_cid=local_cid,
                           centroids=centroids, route="round-robin",
                           buckets=(4,), fill_threshold=4,
                           wait_limit_s=1e-3, fifo_depth=2, hedge=hedge)
    # pre-compile the origin-merge executable: a mid-run jit trace would
    # stall the poll loop for ~100ms and contaminate the EWMA
    topo._warm_merge()
    return topo, groups


def _queries(n, dim=4):
    rng = np.random.default_rng(11)
    q = rng.normal(0, 5.0, (n, dim)).astype(np.float32)
    q[:, 0] = np.arange(n)
    return q


def test_topology_hedging_reissues_and_stays_correct():
    n = 32
    q = _queries(n)
    topo, groups = _fake_topo(n, slow_s=0.25,
                              hedge=HedgeConfig(k=2.0, max_reissue=1,
                                                alpha=0.3))
    rep = topo.run(q)
    # results identical to an unhedged run: every query's encoded id
    # survives the scatter, the race, and the origin merge
    routed = rep.ids[:, 0] >= 0
    np.testing.assert_array_equal(rep.ids[routed][:, 0],
                                  np.nonzero(routed)[0])
    assert rep.n_shed == 0
    # the slow replica's flushes went overdue and were hedged onto the
    # fast replica of the SAME shard; the losers were dropped un-deposited
    assert rep.n_reissued >= 1
    assert rep.n_duplicate_drops >= 1
    assert rep.n_duplicate_drops <= rep.n_reissued
    # per-shard EWMA was fed by real completions on both shards
    assert len(rep.shard_ewma_ms) == 2
    assert all(np.isfinite(v) for v in rep.shard_ewma_ms)
    # a 0.25s straggler hedged at ~2x a ~10ms EWMA: the tail must land
    # far below the unhedged 250ms floor (generous margin for CI noise)
    assert rep.p99_ms < 200.0, rep.p99_ms


def test_topology_hedging_accounting_all_zero_when_disabled():
    n = 16
    q = _queries(n)
    topo, _ = _fake_topo(n)                # no hedge config
    rep = topo.run(q)
    assert rep.n_reissued == 0
    assert rep.n_duplicate_drops == 0
    assert rep.shard_ewma_ms == []
    routed = rep.ids[:, 0] >= 0
    np.testing.assert_array_equal(rep.ids[routed][:, 0],
                                  np.nonzero(routed)[0])


def test_hedge_requires_sharded_topology():
    eng = _FakeShardEngine(8, vectors=jnp.zeros((4, 4), jnp.float32))
    with pytest.raises(ValueError, match="hedge"):
        ServingTopology([[eng]], buckets=(4,), fill_threshold=4,
                        hedge=HedgeConfig())
