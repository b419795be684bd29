"""Spans, scopes and counters of the serving path (``core/obs.py``).

A run under ``jax.profiler`` leaves ``serve.*`` spans on the host plane
of the trace, nested and keyed by flush id; the serving executable's ops
carry the stage scopes in their metadata; ``TopologyReport.counters`` is
the arithmetic of the ``SearchStats`` each flush returned, on the
replicated, sharded and mesh paths; and none of it changes an answer."""

import gc
import json
import os
import re
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import compact_index, engine, obs, placement
from repro.core.execbackend import InProcBackend
from repro.core.topology import TopologyConfig
from repro.data.synthetic import clustered_vectors, query_set

ROOT = Path(__file__).resolve().parents[1]
STREAM = dict(buckets=(8, 16), fill_threshold=16, wait_limit_s=1e-3,
              fifo_depth=2)


@pytest.fixture(scope="module")
def eng_q():
    x, _ = clustered_vectors(3, 2000, 32, 8)
    q = query_set(3, x, 37)
    icfg = compact_index.IndexConfig(dim=32, n_clusters=8, degree=8, knn_k=16)
    scfg = engine.SearchConfig(nprobe=2, ef=16, k=5)
    eng = engine.PIMCQGEngine.build(jax.random.PRNGKey(0), x, icfg, scfg,
                                    n_shards=2)
    return eng, q


class Recording(InProcBackend):
    """The in-process backend, keeping the stats of every execution."""

    def __init__(self):
        self.stats = []

    def search(self, engine, queries, *, pad_to):
        out = super().search(engine, queries, pad_to=pad_to)
        self.stats.append(out[1])
        return out

    def search_probed(self, engine, queries, probe, *, pad_to):
        out = super().search_probed(engine, queries, probe, pad_to=pad_to)
        self.stats.append(out[1])
        return out


def expected_counters(stats) -> dict:
    """The counter formulas, written out over each execution's hops."""
    want = dict(flushes=0, lane_slots=0, live_lanes=0, hops=0,
                slot_hops=0, dropped_lanes=0)
    for st in stats:
        want["flushes"] += 1
        hops = np.asarray(st.hops)
        devs = hops.reshape(-1, *hops.shape[-2:])   # (devices, S, L)
        for h in devs:
            want["lane_slots"] += h.size
            want["live_lanes"] += int((h > 0).sum())
            want["hops"] += int(h.sum())
            want["slot_hops"] += h.size * int(h.max())
        want["dropped_lanes"] += int(np.asarray(st.dropped_lanes).sum())
    return want


def traced(fn):
    """Run ``fn`` under the profiler; (its result, the host events as
    (name, start_ns, end_ns, args))."""
    d = tempfile.mkdtemp(prefix="obs_trace_")
    jax.profiler.start_trace(d)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(d).rglob("*.xplane.pb"))[-1]
    events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
              for p in ProfileData.from_file(str(path)).planes
              if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("serve.")]
    return out, sorted(events, key=lambda e: e[1])


def inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------

def test_run_spans_nest_and_match_the_flushes(eng_q):
    eng, q = eng_q
    topo = TopologyConfig(**STREAM).build(eng)
    topo.warm()
    rep, ev = traced(lambda: topo.run(q))
    flush = [e for e in ev if e[0] == "serve.flush"]
    finish = [e for e in ev if e[0] == "serve.finish"]
    # one flush span per flush, in dispatch order, ids matching the report
    assert [e[3]["flush"] for e in flush] == list(range(rep.n_flushes))
    assert [e[3]["rows"] for e in flush] == rep.flush_sizes
    assert all(e[3]["bucket"] == min(b for b in STREAM["buckets"]
                                     if b >= e[3]["rows"]) for e in flush)
    assert sorted(e[3]["flush"] for e in finish) == list(range(rep.n_flushes))
    # serve.dispatch inside serve.flush, serve.block inside serve.finish
    for name, outer in (("serve.dispatch", flush), ("serve.block", finish)):
        got = [e for e in ev if e[0] == name]
        assert len(got) == len(outer)
        assert all(inside(g, o) for g, o in zip(got, outer))
    # a flush is finished after it was dispatched
    start = {e[3]["flush"]: e[1] for e in flush}
    assert all(e[1] >= start[e[3]["flush"]] for e in finish)


def test_naps_are_idle_stretches_not_loop_iterations(eng_q):
    eng, q = eng_q
    topo = TopologyConfig(**STREAM).build(eng)
    topo.warm()
    arr = np.arange(len(q)) * 5e-3           # sparse open-loop arrivals
    ticks = []
    rep, ev = traced(lambda: topo.run(q, arr, ticker=ticks.append))
    naps = [e for e in ev if e[0] == "serve.nap"]
    work = [e for e in ev if e[0] in ("serve.flush", "serve.finish")]
    assert naps and len(ticks) > 4 * len(naps)
    # a stretch ends where work starts: no nap overlaps a work span
    for n in naps:
        assert not any(w[1] < n[2] and n[1] < w[2] for w in work)
    assert len(naps) <= len(work) + 1


def test_sheds_and_sharded_stages_are_spans(eng_q):
    eng, q = eng_q
    topo = TopologyConfig(shards=2, admission_depth=4, **STREAM).build(eng)
    topo.warm()
    rep, ev = traced(lambda: topo.run(q))
    names = {e[0] for e in ev}
    assert rep.n_shed > 0
    assert sum(e[0] == "serve.shed" for e in ev) == rep.n_shed
    assert {"serve.route", "serve.merge"} <= names
    assert sum(e[3]["rows"] for e in ev if e[0] == "serve.merge") \
        == rep.n_admitted - rep.n_unrouted


def test_gc_spans_count_collections_and_unhook():
    c = obs.Counters()
    n_hooks = len(gc.callbacks)
    with obs.gc_spans(c):
        assert len(gc.callbacks) == n_hooks + 1
        gc.collect()
        gc.collect()
    assert len(gc.callbacks) == n_hooks
    assert c.gc_collections == 2 and c.gc_s > 0
    gc.collect()
    assert c.gc_collections == 2


def test_run_counts_the_collector_inside_it(eng_q):
    eng, q = eng_q
    topo = TopologyConfig(**STREAM).build(eng)
    done = []

    def collect_once(t):
        if not done:
            done.append(gc.collect())

    rep, ev = traced(lambda: topo.run(q, ticker=collect_once))
    assert rep.counters["gc_collections"] >= 1
    gc_spans = [e for e in ev if e[0] == "serve.gc"]
    assert len(gc_spans) == rep.counters["gc_collections"]
    assert any(e[3]["generation"] == 2 for e in gc_spans)
    assert all("collected" in e[3] for e in gc_spans)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards,replicas", [(1, 1), (1, 2), (2, 1)])
def test_counters_are_the_formulas_over_the_flushes_stats(eng_q, shards,
                                                          replicas):
    eng, q = eng_q
    rec = Recording()
    topo = TopologyConfig(shards=shards, replicas=replicas, exec=rec,
                          **STREAM).build(eng)
    rep = topo.run(q)
    assert len(rec.stats) == rep.n_flushes
    got = {k: v for k, v in rep.counters.items() if not k.startswith("gc")}
    assert got == expected_counters(rec.stats)
    # 2 inner shards x the bucket's lane capacity per flush; the lanes of
    # real probes are live
    assert got["lane_slots"] >= got["live_lanes"] > 0
    assert got["slot_hops"] >= got["hops"] > 0


def test_counters_match_the_lane_arithmetic(eng_q):
    """Whole 16-query flushes at nprobe 2 over 2 inner shards: capacity
    ceil(16 * 2 / 2 * 2.0) = 32 slots a shard, 32 live lanes a flush."""
    eng, q = eng_q
    topo = TopologyConfig(**STREAM).build(eng)
    q32 = np.concatenate([q, q])[:32]
    rep = topo.run(q32)
    assert rep.flush_sizes == [16, 16]
    c = rep.counters
    assert c["lane_slots"] == 2 * 2 * 32
    assert c["live_lanes"] == 2 * 16 * 2 - c["dropped_lanes"]


def test_one_shard_whole_flushes_fill_every_lane_slot(eng_q):
    """One inner shard: the lane buffer is capped at the Q * P lanes a
    flush has, so whole 16-query flushes at nprobe 2 leave no dead slot."""
    eng, q = eng_q
    sizes = np.asarray(eng.index.n_valid).astype(np.float64)
    one = engine.PIMCQGEngine(eng.index, eng.host,
                              placement.greedy_place(sizes, sizes, 1),
                              eng.icfg, eng.scfg)
    topo = TopologyConfig(**STREAM).build(one)
    rep = topo.run(np.concatenate([q, q])[:32])
    assert rep.flush_sizes == [16, 16]
    c = rep.counters
    assert c["dropped_lanes"] == 0
    assert c["lane_slots"] == c["live_lanes"] == 2 * 16 * 2


def test_mesh_counters_on_four_virtual_devices():
    script = textwrap.dedent("""
        import json
        import jax, numpy as np
        from repro.core import compact_index, engine
        from repro.core.execbackend import MeshBackend
        from repro.core.topology import TopologyConfig
        from repro.data.synthetic import clustered_vectors, query_set

        class Recording(MeshBackend):
            stats = []
            def search_scattered(self, queries, tables, *, pad_to):
                out = super().search_scattered(queries, tables,
                                               pad_to=pad_to)
                self.stats.append(jax.tree.map(np.asarray, out[1]))
                return out

        x, _ = clustered_vectors(3, 2000, 32, 8)
        q = query_set(3, x, 37)
        eng = engine.PIMCQGEngine.build(
            jax.random.PRNGKey(0), x,
            compact_index.IndexConfig(dim=32, n_clusters=8, degree=8,
                                      knn_k=16),
            engine.SearchConfig(nprobe=2, ef=16, k=5), n_shards=2)
        rec = Recording()
        rep = TopologyConfig(shards=4, exec=rec, buckets=(8, 16),
                             fill_threshold=16, wait_limit_s=1e-3,
                             fifo_depth=2).build(eng).run(q)
        ref = TopologyConfig(shards=4, buckets=(8, 16), fill_threshold=16,
                             wait_limit_s=1e-3, fifo_depth=2
                             ).build(eng).run(q)
        print(json.dumps({
            "counters": rep.counters, "n_flushes": rep.n_flushes,
            "devices": len(jax.devices()),
            "same_ids": bool((rep.ids == ref.ids).all()),
            "hops": [s.hops.tolist() for s in rec.stats],
            "dropped": [s.dropped_lanes.tolist() for s in rec.stats]}))
        """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4 and res["same_ids"]
    stats = [engine.SearchStats(np.asarray(h), np.asarray(d))
             for h, d in zip(res["hops"], res["dropped"])]
    assert len(stats) == res["n_flushes"]
    assert np.asarray(stats[0].hops).shape[0] == 4      # one row a device
    got = {k: v for k, v in res["counters"].items()
           if not k.startswith("gc")}
    assert got == expected_counters(stats)


# ---------------------------------------------------------------------------
# scopes, and what the instrumentation must not change
# ---------------------------------------------------------------------------

def test_search_step_ops_carry_the_stage_scopes(eng_q):
    eng, q = eng_q
    fn = eng._build_search_fn(8)
    hlo = fn.lower(eng.placed, eng.index.centroids, eng.index.rotation,
                   eng.host.vectors, np.zeros((8, 32), np.float32),
                   jnp.int32(8)).compile().as_text()
    # name-stack components, with transform wrappers (vmap(...), jit(...))
    # taken off
    stacks = [[re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in s.split("/")]
              for s in set(re.findall(r'op_name="([^"]+)"', hlo))]
    for stage in ("cluster_filter", "route_lanes", "prepare_lanes",
                  "beam_search", "rerank"):
        assert any(stage in s for s in stacks), stage
    loop = [s for s in stacks if "beam_search" in s and "body" in s]
    for sub in ("visited", "expand", "rank", "select"):
        assert any(sub in s for s in loop), sub


def test_profiler_on_and_off_give_the_same_answers(eng_q):
    eng, q = eng_q
    topo = TopologyConfig(shards=2, **STREAM).build(eng)
    arr = np.arange(len(q)) * 3e-4
    off = topo.run(q, arr)
    on, ev = traced(lambda: topo.run(q, arr))
    assert ev
    np.testing.assert_array_equal(on.ids, off.ids)
    np.testing.assert_array_equal(on.dists, off.dists)
