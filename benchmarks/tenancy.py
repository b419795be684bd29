"""Tenancy benchmark — noisy-neighbor isolation and weighted fairness
(ISSUE 8).

Drives the tenant-aware serving spine (``core/topology.py``: TenantSpec
registry + DWRR admission) with deterministic fake shard engines (a
serial "device" with a fixed per-flush service time, the
tests/test_topology.py double), then replays the same contracts on the
calibrated ``EventSimulator`` tenant overlay. The claims:

  * Noisy-neighbor isolation: an aggressor tenant offering 8x the
    victim's load (well past fleet capacity) cannot push the weighted
    victim's p99 above 1.5x its ISOLATED p99, and sheds fall entirely on
    the aggressor. A FIFO-contrast row (same stream, no tenant registry)
    shows what the pre-refactor single queue did to the victim — context,
    not a gated claim.

  * Weighted fairness: two equally-overloaded tenants with 3:1 DWRR
    weights are served within 20% of the 3:1 ratio (dealt counts on the
    real topology, completions on the simulator).

  * The calibrated simulator overlay (host prep as the DWRR-gated
    bottleneck, costed from the doubles' service rate) reproduces both
    claims deterministically.
"""

from __future__ import annotations

import time
import types

import numpy as np
import jax.numpy as jnp

from repro.core.pipeline import EventSimulator, LinkModel, StageCosts
from repro.core.topology import ServingTopology, TenantSpec
from .common import check, fmt_row, smoke_cap

SERVICE_S = 0.02         # per-flush service time of one fake shard device
FLUSH = 4                # flush quantum (queries per device batch)
N_SHARDS = 2
WINDOW_S = smoke_cap(2.0, 0.6)     # offered-stream duration per scenario
VICTIM_QPS = 50.0
AGGRESSOR_MULT = 8.0     # the ISSUE 8 noisy-neighbor figure
ISO_P99_BOUND = 1.5
FAIR_WEIGHTS = (3.0, 1.0)
FAIR_TOL = 0.2


# ---------------------------------------------------------------------------
# minimal deterministic doubles (the tests/test_topology.py fakes, inlined:
# benchmarks run without the test tree on sys.path)
# ---------------------------------------------------------------------------

class _LazyArray:
    def __init__(self, a, t_done, on_materialize=None):
        self._a = a
        self._t_done = t_done
        self._cb = on_materialize

    def is_ready(self):
        return time.perf_counter() >= self._t_done

    def __array__(self, dtype=None, *_, **__):
        wait = self._t_done - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        if self._cb is not None:
            cb, self._cb = self._cb, None
            cb()
        return self._a if dtype is None else self._a.astype(dtype)


class FakeShardEngine:
    """Serial fake device: search_probed echoes the query index (encoded
    in column 0) after a fixed service time — scheduling is real, search
    is free, so every latency in the report is pure queueing/service."""

    def __init__(self, n_clusters, k=3, nprobe=2, service_s=SERVICE_S,
                 vectors=None):
        self.scfg = types.SimpleNamespace(k=k, nprobe=nprobe, mode="fake")
        self.index = types.SimpleNamespace(n_clusters=n_clusters)
        self.host = types.SimpleNamespace(vectors=vectors)
        self.buckets = ()
        self.service_s = service_s
        self.t_free = 0.0
        self.outstanding = 0

    @property
    def compile_count(self):
        return 0

    def search_probed(self, q, probes, *, pad_to=None, trim=True):
        q = np.asarray(q)
        t_done = max(time.perf_counter(), self.t_free) + self.service_s
        self.t_free = t_done
        self.outstanding += 1
        ids = np.repeat(q[:, :1].astype(np.int32), self.scfg.k, axis=1)
        dists = np.zeros((len(q), self.scfg.k), np.float32)

        def done():
            self.outstanding -= 1

        return types.SimpleNamespace(ids=_LazyArray(ids, t_done, done),
                                     dists=_LazyArray(dists, t_done)), None


def _fake_topology(n_queries, tenants=None, shed_deadline_s=None):
    C, dim = 8, 4
    per = C // N_SHARDS
    part_of = np.repeat(np.arange(N_SHARDS), per).astype(np.int32)
    local_cid = np.tile(np.arange(per), N_SHARDS).astype(np.int32)
    rng = np.random.default_rng(7)
    centroids = rng.normal(0, 5.0, (C, dim)).astype(np.float32)
    vectors = jnp.zeros((n_queries, dim), jnp.float32)
    groups = [[FakeShardEngine(per, vectors=vectors)]
              for _ in range(N_SHARDS)]
    return ServingTopology(groups, part_of=part_of, local_cid=local_cid,
                           centroids=centroids, buckets=(FLUSH,),
                           fill_threshold=FLUSH, wait_limit_s=1e-3,
                           fifo_depth=1, admission_depth=100_000,
                           shed_deadline_s=shed_deadline_s,
                           tenants=tenants)


def _stream(rng, n, dim=4, window=WINDOW_S):
    q = rng.normal(0, 5.0, (n, dim)).astype(np.float32)
    q[:, 0] = np.arange(n)
    arr = np.sort(rng.uniform(0.0, window, n))
    return q, arr


def _merge(streams):
    """Merge per-tenant (q, arr, label) streams time-ordered."""
    q = np.concatenate([s[0] for s in streams])
    q[:, 0] = np.arange(len(q))          # re-encode global indices
    arr = np.concatenate([s[1] for s in streams])
    labels = np.concatenate([np.full(len(s[0]), s[2], object)
                             for s in streams])
    order = np.argsort(arr, kind="stable")
    return q[order], arr[order], list(labels[order])


def run(verbose: bool = True) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)

    # -- scenario A: noisy neighbor on the real topology ---------------------
    n_v = int(VICTIM_QPS * WINDOW_S)
    n_a = int(AGGRESSOR_MULT * VICTIM_QPS * WINDOW_S)
    vq, varr = _stream(rng, n_v)
    aq, aarr = _stream(rng, n_a)
    specs = [TenantSpec("victim", weight=4.0),
             TenantSpec("aggressor", weight=1.0, deadline_s=0.05)]
    q, arr, labels = _merge([(vq, varr, "victim"), (aq, aarr, "aggressor")])

    iso = _fake_topology(n_v, tenants=[specs[0]]).run(vq, varr,
                                                      tenant="victim")
    p99_iso = iso.tenants["victim"]["p99_ms"]
    shared = _fake_topology(len(q), tenants=specs).run(q, arr,
                                                       tenant=labels)
    v, a = shared.tenants["victim"], shared.tenants["aggressor"]
    rows.append(fmt_row(
        "tenancy_isolation", 1e6 / max(shared.qps, 1e-9),
        f"victim_p99={v['p99_ms']:.1f}ms iso_p99={p99_iso:.1f}ms "
        f"ratio={v['p99_ms'] / p99_iso:.2f} victim_shed={v['n_shed']} "
        f"aggr_shed={a['n_shed']}/{n_a} "
        f"aggr_goodput={a['qps']:.0f}qps"))
    check(v["n_shed"] == 0,
          f"victim shed {v['n_shed']} queries under the aggressor — "
          f"isolation failed")
    check(a["n_shed"] > 0,
          "the aggressor shed nothing: the scenario is not overloaded "
          "enough to say anything about isolation")
    check(v["p99_ms"] <= ISO_P99_BOUND * p99_iso,
          f"victim p99 {v['p99_ms']:.1f}ms exceeds {ISO_P99_BOUND}x its "
          f"isolated p99 {p99_iso:.1f}ms under an "
          f"{AGGRESSOR_MULT:.0f}x-load aggressor")

    # FIFO contrast (context, not gated): the same stream through the
    # pre-refactor single queue — one global deadline, no weights
    fifo = _fake_topology(len(q), shed_deadline_s=0.05).run(q, arr)
    vrows = np.asarray([l == "victim" for l in labels])
    fifo_v_lat = fifo.latency_s[vrows]
    fifo_v_shed = int(fifo.shed[vrows].sum())
    fifo_p99 = (float(np.nanpercentile(fifo_v_lat, 99)) * 1e3
                if np.isfinite(fifo_v_lat).any() else float("inf"))
    rows.append(fmt_row(
        "tenancy_fifo_contrast", 0.0,
        f"victim_p99_fifo={fifo_p99:.1f}ms victim_shed_fifo={fifo_v_shed} "
        f"(vs dwrr: {v['p99_ms']:.1f}ms / {v['n_shed']})"))

    # -- scenario B: weighted fairness on the real topology ------------------
    per = int(smoke_cap(200, 120))
    hi_q, _ = _stream(rng, per)
    lo_q, _ = _stream(rng, per)
    fspecs = [TenantSpec("hi", weight=FAIR_WEIGHTS[0], deadline_s=0.3),
              TenantSpec("lo", weight=FAIR_WEIGHTS[1], deadline_s=0.3)]
    fq, farr, flabels = _merge([(hi_q, np.zeros(per), "hi"),
                                (lo_q, np.zeros(per), "lo")])
    frep = _fake_topology(len(fq), tenants=fspecs).run(fq, farr,
                                                       tenant=flabels)
    hi, lo = frep.tenants["hi"], frep.tenants["lo"]
    want = FAIR_WEIGHTS[0] / FAIR_WEIGHTS[1]
    ratio = hi["dealt"] / max(lo["dealt"], 1)
    rows.append(fmt_row(
        "tenancy_fairness", 0.0,
        f"dealt_hi={hi['dealt']} dealt_lo={lo['dealt']} ratio={ratio:.2f} "
        f"want={want:.1f} shed_hi={hi['n_shed']} shed_lo={lo['n_shed']}"))
    check(hi["n_shed"] > 0 and lo["n_shed"] > 0,
          "fairness scenario must saturate BOTH tenants")
    check((1 - FAIR_TOL) * want <= ratio <= (1 + FAIR_TOL) * want,
          f"dealt ratio {ratio:.2f} strays more than {FAIR_TOL:.0%} from "
          f"the {want:.1f}:1 weight ratio")

    # -- calibrated simulator overlay ----------------------------------------
    # The same contracts replayed at PIM-native rates: a prep-bound tier
    # (host LUT prep 50us/query => ~20k q/s through the DWRR-gated stage,
    # PU scan 10us/query, rerank 2us/query, UPMEM-like link) with the
    # victim at 4k q/s and the aggressor at 8x that — fully deterministic,
    # so the claims gate on exact event-driven arithmetic rather than
    # wall-clock sleeps.
    costs = StageCosts(
        t_pre=lambda n: 5e-5 * n + 1e-6,
        t_proc=lambda n: 1e-5 * n + 5e-6,
        t_post=lambda n: 2e-6 * n + 1e-6,
        link=LinkModel(setup_s=5e-6, bw_bytes_s=1e9, knee_bytes=8192,
                       congestion=0.3),
        query_bytes=512, result_bytes=512)
    sim = EventSimulator(n_pus=4, costs=costs, rerank_workers=4)
    srng = np.random.default_rng(3)
    sn_a = 4000
    sarrs, stids, spuss = [], [], []
    for t, rate in enumerate([4000.0, 32000.0]):   # aggressor = 8x victim
        n = int(rate * 0.125)
        sarrs.append(np.sort(srng.uniform(0.0, 0.125, n)))
        stids.append(np.full(n, t, int))
        spuss.append(srng.integers(0, 4, n))
    sarr = np.concatenate(sarrs)
    spus = np.concatenate(spuss)
    stid = np.concatenate(stids)
    order = np.argsort(sarr, kind="stable")
    sarr, spus, stid = sarr[order], spus[order], stid[order]
    kw = dict(threshold=8, wait_limit_s=1e-3, shed_deadline_s=2e-3)
    s_shared = sim.dynamic(sarr, spus, tenant_of=stid,
                           tenant_weights=[4.0, 1.0],
                           tenant_deadline_s=[1.0, 2e-3], **kw)
    sv = stid == 0
    s_iso = sim.dynamic(sarr[sv], spus[sv],
                        tenant_of=np.zeros(int(sv.sum()), int),
                        tenant_weights=[4.0], tenant_deadline_s=[1.0],
                        **kw)
    sim_ratio = s_shared.tenant_p99_s[0] / s_iso.tenant_p99_s[0]
    rows.append(fmt_row(
        "tenancy_sim_isolation", 0.0,
        f"victim_p99={s_shared.tenant_p99_s[0] * 1e3:.2f}ms "
        f"iso_p99={s_iso.tenant_p99_s[0] * 1e3:.2f}ms "
        f"ratio={sim_ratio:.2f} victim_shed={s_shared.tenant_shed[0]} "
        f"aggr_shed={s_shared.tenant_shed[1]}/{sn_a}"))
    check(s_shared.tenant_shed[0] == 0,
          "simulator overlay: victim shed under the aggressor")
    check(s_shared.tenant_shed[1] > 0,
          "simulator overlay: aggressor shed nothing — not overloaded")
    check(sim_ratio <= ISO_P99_BOUND,
          f"simulator overlay: victim p99 ratio {sim_ratio:.2f} exceeds "
          f"{ISO_P99_BOUND}x isolated")

    # fairness on the simulator: both tenants offer 30k q/s against the
    # ~20k q/s prep bottleneck (3x total overload), 3:1 weights
    n_f = 3000                       # 30k q/s per tenant over 0.1 s
    farr_s = np.sort(srng.uniform(0.0, 0.1, 2 * n_f))
    fpus = srng.integers(0, 4, 2 * n_f)
    ftid = (np.arange(2 * n_f) % 2).astype(int)
    s_fair = sim.dynamic(farr_s, fpus, tenant_of=ftid,
                         tenant_weights=list(FAIR_WEIGHTS),
                         tenant_deadline_s=[20e-3, 20e-3], threshold=8,
                         wait_limit_s=1e-3, shed_deadline_s=20e-3)
    s_ratio = s_fair.tenant_queries[0] / max(s_fair.tenant_queries[1], 1)
    rows.append(fmt_row(
        "tenancy_sim_fairness", 0.0,
        f"done_hi={s_fair.tenant_queries[0]} "
        f"done_lo={s_fair.tenant_queries[1]} ratio={s_ratio:.2f} "
        f"want={want:.1f} shed={s_fair.tenant_shed}"))
    check(s_fair.tenant_shed[0] > 0 and s_fair.tenant_shed[1] > 0,
          "simulator fairness scenario must saturate both tenants")
    check((1 - FAIR_TOL) * want <= s_ratio <= (1 + FAIR_TOL) * want,
          f"simulator completion ratio {s_ratio:.2f} strays more than "
          f"{FAIR_TOL:.0%} from {want:.1f}:1")

    if verbose:
        for r in rows:
            print(r)
    return rows
