"""Fig 18 — multi-node scale-out: MEASURED scatter/gather over a sharded
fleet, now with the scatter -> search -> gather path ALSO executed as real
device-mesh collectives (ISSUE 6 mesh execution backend) and the alpha-beta
network model CALIBRATED against measured ``all_gather`` timings instead of
assumed datasheet constants.

Three measurement tiers, one model:

  * in-process sharded fleet + hybrid 2x2 (ISSUE 4/5 machinery) — routing
    and merge correctness, parity with the single engine;
  * the mesh execution backend (``exec="mesh"``) at shards {2, 4[, 8]} on
    an ``--xla_force_host_platform_device_count`` mesh — the SAME rows,
    through ``shard_map`` + ``jax.lax.all_gather`` lowered collectives
    (benchmarks/run.py forces 8 host devices; rows are skipped, loudly,
    when the process has fewer than 2);
  * an ``all_gather`` microbenchmark over device counts x payload sizes,
    least-squares fitted to ``t = alpha + beta * (D-1) * nbytes``.

The fitted (alpha, beta) drive ``calibrated_qps`` — the scale-out
prediction whose dip/recovery/near-linear claims gate CI, with per-point
relative residuals reported in the rows (and bounded by a claim) so the
fit quality itself is load-bearing. The 400 Gbps InfiniBand overlay
(``predicted_qps``) is kept as the unasserted analytic reference.

Model claims kept from the paper: a dip at 2 nodes (network cost + the
replication overhead below), then near-linear 2->32 as query parallelism
dominates.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import engine
from repro.core.fleet import partition_engine, topology
from repro.core.topology import TopologyConfig
from .common import (SMOKE, build_engine, check, fmt_row, make_workload,
                     timed_qps)

IB_BW = 400e9 / 8          # bytes/s
IB_LAT = 2e-6              # per message

# Scale-out efficiency ceiling: per-node search capacity is ~8% below the
# single-node figure once the node also runs scatter/gather bookkeeping.
SCALE_EFF = 0.92

# The paper's 2-node dip: at exactly 2 nodes every hot (high-freq)
# cluster whose probes straddle the partition boundary is effectively
# served twice — replicated work and doubled gather traffic on the
# origin — while the query-parallelism win is still only 2x. The paper's
# Fig 18 measures this as ~20% of the doubled capacity lost; from 4 nodes
# up the boundary share per node shrinks and the dip vanishes.
#
# Since ISSUE 10 the factor is MEASURED per run: the fig18_sharded2_repl
# row serves the same stream through a hot-replicated 2-node topology
# (``replicate_hot`` + owner routing collapses the straddling probe
# sets), and plain/replicated goodput gives the dip directly. This
# constant is the documented FALLBACK used only when the model functions
# are called without a measurement (e.g. standalone imports).
TWO_NODE_REPLICATION_FACTOR = 0.8

# hot set for the measured 2-node replication row: half the 24 synthetic
# clusters, each resident on both nodes (replica_factor=2)
REPL_HOT_2NODE = 12

MODEL_NODES = (1, 2, 4, 8, 16, 32)

# microbenchmark grid: device counts x per-device payload bytes
AG_DEVICES = (2, 4, 8)
AG_PAYLOADS = (4096, 65536, 524288)


def predicted_qps(nodes: int, qps1: float, q_bytes: int, cand_bytes: int,
                  nprobe: int,
                  two_node_factor: float = TWO_NODE_REPLICATION_FACTOR
                  ) -> float:
    """Alpha-beta IB network model of sharded scatter/gather throughput
    (datasheet constants — the UNASSERTED analytic overlay; the asserted
    model is ``calibrated_qps`` below).

    Each query fans out to <= min(nprobe, nodes-1) remote nodes (query
    scatter) and their candidates gather back to the origin; node-local
    search capacity scales linearly while the NIC serializes per-origin
    traffic. Throughput = min(compute scale-out, NIC serialization), with
    ``two_node_factor`` (measured in run(); the module constant is the
    fallback) applied at the 2-node point."""
    if nodes == 1:
        return qps1
    per_q_net = 2 * IB_LAT + (q_bytes + cand_bytes) * \
        min(nprobe, nodes - 1) / IB_BW
    qps = min(nodes * qps1 * SCALE_EFF, nodes / per_q_net)
    if nodes == 2:
        qps *= two_node_factor
    return qps


def calibrated_qps(nodes: int, qps1: float, q_bytes: int, cand_bytes: int,
                   nprobe: int, alpha: float, beta: float,
                   flush: int = 64,
                   two_node_factor: float = TWO_NODE_REPLICATION_FACTOR
                   ) -> float:
    """The same throughput structure as ``predicted_qps`` but with the
    collective cost MEASURED: scattering a ``flush``-query batch to ``fan``
    owners and gathering their candidates back is ``fan`` hops of the
    fitted ring law, ``fan * (alpha + beta * flush * payload)`` seconds,
    and the fixed cost amortizes over the whole flush — exactly how the
    serving tier dispatches."""
    if nodes == 1:
        return qps1
    fan = min(nprobe, nodes - 1)
    per_q_net = fan * (alpha + beta * flush * (q_bytes + cand_bytes)) / flush
    qps = min(nodes * qps1 * SCALE_EFF, nodes / per_q_net)
    if nodes == 2:
        qps *= two_node_factor
    return qps


def allgather_microbench(ndev: int) -> list[tuple[int, int, float]]:
    """Measured wall time of one jitted shard_map ``all_gather`` step per
    (device count D, per-device payload nbytes): min-of-k over committed
    inputs, so dispatch overhead (the alpha being fitted) is included and
    host->device transfer is not. Returns [(D, nbytes, seconds)]."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    reps = 3 if SMOKE else 7
    pts = []
    for d in [d for d in AG_DEVICES if d <= ndev]:
        mesh = Mesh(np.asarray(jax.devices()[:d]), ("gx",))
        fn = jax.jit(jax.shard_map(lambda v: jax.lax.all_gather(v, "gx"),
                               mesh=mesh, in_specs=P("gx"), out_specs=P(),
                               check_vma=False))
        for nb in AG_PAYLOADS:
            x = jax.device_put(jnp.zeros((d * (nb // 4),), jnp.float32),
                               NamedSharding(mesh, P("gx")))
            jax.block_until_ready(fn(x))               # compile + warm
            best = np.inf
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(x))
                best = min(best, time.perf_counter() - t0)
            pts.append((d, nb, best))
    return pts


def fit_alpha_beta(pts):
    """Least-squares fit of the Hockney ring-collective model

        t = (D - 1) * (alpha + beta * nbytes)

    over the microbenchmark grid — an all_gather over D devices makes D-1
    hops, each paying a fixed alpha plus nbytes at 1/beta bandwidth (this
    matches the measured per-device-count scaling of the 4KB points, which
    a single global alpha cannot). Weighted by 1/t so the fit minimizes
    RELATIVE error: t spans ~70us..5ms and an absolute fit would buy the
    512KB points their accuracy with >100% error at the latency floor.
    Returns (alpha, beta, rel_residuals); alpha clamped non-negative, beta
    asserted positive by the caller."""
    A = np.array([[d - 1.0, (d - 1) * nb] for d, nb, _ in pts])
    t = np.array([p[2] for p in pts])
    wgt = 1.0 / t
    coef, *_ = np.linalg.lstsq(A * wgt[:, None], t * wgt, rcond=None)
    alpha = float(max(coef[0], 0.0))
    beta = float(coef[1])
    pred = A @ np.array([alpha, beta])
    rel = (pred - t) / t
    return alpha, beta, rel


def run(verbose: bool = True) -> list[str]:
    import jax

    w = make_workload("SIFT")
    scfg = engine.SearchConfig(nprobe=4, ef=40, k=10)
    eng = build_engine(w, scfg)
    (res1, _), qps1, _ = timed_qps(lambda q: eng.search(q), w.q)
    sync_ids = np.asarray(res1.ids)

    rows = []
    # -- measured: scatter/gather over the sharded fleet --------------------
    # 24 clusters -> partitions at 2/4/8 nodes (smoke: 2/4)
    measured_nodes = (2, 4) if SMOKE else (2, 4, 8)
    rep_2node = None
    for nodes in measured_nodes:
        fleet = partition_engine(eng, nodes, buckets=(len(w.q),),
                                 fill_threshold=len(w.q), wait_limit_s=5e-3)
        fleet.run(w.q)                              # warm the executables
        rep = fleet.run(w.q)
        # parity holds because neither side overflows lane capacity here
        # (balanced synthetic clusters, lane_capacity_factor=2 headroom);
        # see the ShardedFleet docstring for the drop caveat
        check((rep.ids == sync_ids).all(),
              f"sharded fleet ids diverge from single engine at "
              f"{nodes} nodes")
        shares = [d["queries"] for d in rep.per_engine]
        rows.append(fmt_row(
            f"fig18_sharded{nodes}", 1e6 / max(rep.qps, 1e-9),
            f"qps={rep.qps:.0f} fanout={rep.fanout_mean:.2f} "
            f"scatter_flushes={rep.n_flushes} merges={rep.n_merges} "
            f"per_engine_q={shares} ids_match_single=1.000"))
        check(0 < rep.fanout_mean <= min(scfg.nprobe, nodes),
              f"fanout {rep.fanout_mean} outside (0, "
              f"{min(scfg.nprobe, nodes)}]")
        if nodes == 2:
            rep_2node = rep

    # -- measured: 2-node hot replication (ISSUE 10) ------------------------
    # the two-node dip, measured instead of assumed: serve the same stream
    # through a plain and a hot-replicated 2-node topology (hot half of
    # the clusters resident on both nodes; heat = per-cluster probe counts
    # of this stream, the histogram TopologyReport.cluster_hits measures).
    # The owner router collapses straddling probe sets onto one node, so
    # plain/replicated goodput IS the dip factor the scale-out models
    # apply at their 2-node point (constant 0.8 = fallback). Buckets are
    # small enough (16) that flush count tracks scattered touches — one
    # whole-stream bucket would pad the difference away.
    two_node_factor = TWO_NODE_REPLICATION_FACTOR
    if rep_2node is not None:
        cents = np.asarray(eng.index.centroids)
        pd2 = ((w.q[:, None, :] - cents[None]) ** 2).sum(-1)
        probes = np.argsort(pd2, axis=1)[:, :scfg.nprobe]
        heat = np.bincount(probes.ravel(),
                           minlength=len(cents)).astype(np.int64)
        pcfg = TopologyConfig(shards=2, buckets=(16,), fill_threshold=16,
                              wait_limit_s=5e-3)
        rcfg = dataclasses.replace(pcfg, replicate_hot=REPL_HOT_2NODE,
                                   replica_factor=2)
        ptopo = pcfg.build(eng, heat=heat)
        rtopo = rcfg.build(eng, heat=heat)
        reps = {}
        for name, t in (("plain", ptopo), ("repl", rtopo)):
            t.warm()
            t.run(w.q)
            reps[name] = t.run(w.q)
            check((reps[name].ids == sync_ids).all(),
                  f"{name} 2-node ids diverge from single engine")
        prep, rrep = reps["plain"], reps["repl"]
        check(rrep.fanout_mean < prep.fanout_mean,
              f"hot replication did not collapse 2-node fanout "
              f"({prep.fanout_mean:.2f} -> {rrep.fanout_mean:.2f})")
        two_node_factor = min(1.0, prep.qps / max(rrep.qps, 1e-9))
        rows.append(fmt_row(
            "fig18_sharded2_repl", 1e6 / max(rrep.qps, 1e-9),
            f"qps={prep.qps:.0f}->{rrep.qps:.0f} fanout="
            f"{prep.fanout_mean:.2f}->{rrep.fanout_mean:.2f} "
            f"hot={REPL_HOT_2NODE}x2 measured_two_node_factor="
            f"{two_node_factor:.2f} (fallback "
            f"{TWO_NODE_REPLICATION_FACTOR}) ids_match_single=1.000"))

    # -- measured: the hybrid point (ISSUE 5) -------------------------------
    # 4 engines arranged as 2 shards x 2 replicas: partition for capacity,
    # replicate each partition for throughput. Parity must still hold, the
    # scatter fanout is bounded by the SHARD count (not the engine count),
    # and both replicas of every shard genuinely share its load.
    topo = topology(eng, shards=2, replicas=2, buckets=(len(w.q),),
                    fill_threshold=len(w.q), wait_limit_s=5e-3)
    topo.run(w.q)                                  # warm the executables
    rep = topo.run(w.q)
    check((rep.ids == sync_ids).all(),
          "hybrid 2x2 topology ids diverge from single engine")
    check(0 < rep.fanout_mean <= min(scfg.nprobe, 2),
          f"hybrid fanout {rep.fanout_mean} outside (0, "
          f"{min(scfg.nprobe, 2)}] — bounded by shards, not engines")
    shares = [d["queries"] for d in rep.per_engine]
    for o in range(2):
        reps = [d["queries"] for d in rep.per_engine if d["shard"] == o]
        check(min(reps) > 0,
              f"hybrid shard {o} left a replica idle: {reps}")
    rows.append(fmt_row(
        "fig18_hybrid2x2", 1e6 / max(rep.qps, 1e-9),
        f"qps={rep.qps:.0f} fanout={rep.fanout_mean:.2f} "
        f"scatter_flushes={rep.n_flushes} merges={rep.n_merges} "
        f"per_engine_q={shares} ids_match_single=1.000"))

    # -- measured: mesh execution backend (ISSUE 6) -------------------------
    # the same scatter/gather rows, but scatter -> search_probed -> gather
    # runs as shard_map-lowered collectives on a real device mesh; parity
    # with the single engine is the end-to-end collective-path check
    ndev = len(jax.devices())
    q_bytes = w.icfg.dim * 4
    cand_bytes = scfg.ef * scfg.nprobe * 8
    mesh_nodes = [n for n in measured_nodes if n <= ndev]
    if ndev < 2:
        rows.append(fmt_row(
            "fig18_mesh_skipped", 0.0,
            f"devices={ndev} (run via benchmarks.run, which forces 8 host "
            f"devices, or set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=N)"))
    for nodes in mesh_nodes:
        mtopo = topology(eng, shards=nodes, exec="mesh",
                         buckets=(len(w.q),), fill_threshold=len(w.q),
                         wait_limit_s=5e-3)
        mtopo.warm()
        mrep = mtopo.run(w.q)
        check((mrep.ids == sync_ids).all(),
              f"mesh backend ids diverge from single engine at "
              f"{nodes} shards")
        shares = [d["queries"] for d in mrep.per_engine]
        rows.append(fmt_row(
            f"fig18_mesh{nodes}", 1e6 / max(mrep.qps, 1e-9),
            f"qps={mrep.qps:.0f} exec=mesh fanout={mrep.fanout_mean:.2f} "
            f"per_shard_q={shares} ids_match_single=1.000"))

    # -- calibration: all_gather microbenchmark -> alpha-beta fit -----------
    alpha = beta = None
    if ndev >= 2:
        pts = allgather_microbench(ndev)
        alpha, beta, rel = fit_alpha_beta(pts)
        for (d, nb, t), r in zip(pts, rel):
            rows.append(fmt_row(
                f"fig18_ag_d{d}_{nb // 1024}kb", t * 1e6,
                f"devices={d} payload_kb={nb // 1024} "
                f"rel_residual={r:+.3f}"))
        med = float(np.median(np.abs(rel)))
        rows.append(fmt_row(
            "fig18_fit", alpha * 1e6,
            f"alpha_us={alpha * 1e6:.1f} beta_s_per_byte={beta:.3e} "
            f"median_abs_rel_residual={med:.3f} "
            f"max_abs_rel_residual={float(np.max(np.abs(rel))):.3f} "
            f"points={len(pts)}"))
        # fit-quality claims: the model must actually describe the data
        check(beta > 0,
              f"fitted bandwidth slope beta={beta:.3e} is not positive — "
              f"the payload grid never left the latency floor")
        check(med <= 0.5,
              f"alpha-beta fit median |rel residual| {med:.2f} > 0.5 — "
              f"the linear collective model does not fit the measurements")

    # -- calibrated scale-out model (asserted) + IB overlay (reference) -----
    if alpha is not None:
        cal = {n: calibrated_qps(n, qps1, q_bytes, cand_bytes, scfg.nprobe,
                                 alpha, beta, flush=len(w.q),
                                 two_node_factor=two_node_factor)
               for n in MODEL_NODES}
        prev = None
        for nodes in MODEL_NODES:
            qps = cal[nodes]
            eff = qps / (nodes * qps1)
            rows.append(fmt_row(
                f"fig18_cal_nodes{nodes}", 1e6 / qps,
                f"qps={qps:.0f} efficiency={eff:.2f}"
                + (f" speedup_vs_prev={qps / prev:.2f}x" if prev else "")))
            prev = qps
        # paper claims, asserted on the CALIBRATED model: the 2-node dip,
        # recovery, then near-linear 2->32
        check(cal[2] / (2 * qps1) < 0.9,
              f"2-node efficiency {cal[2] / (2 * qps1):.2f} shows no dip")
        check(cal[4] / (4 * qps1) > cal[2] / (2 * qps1),
              "efficiency must recover past the 2-node dip")
        check(cal[32] / cal[2] >= 0.7 * 16,
              f"2->32 speedup {cal[32] / cal[2]:.1f}x is not near-linear")

    pred = {n: predicted_qps(n, qps1, q_bytes, cand_bytes, scfg.nprobe,
                             two_node_factor=two_node_factor)
            for n in MODEL_NODES}
    for nodes in MODEL_NODES:
        qps = pred[nodes]
        rows.append(fmt_row(
            f"fig18_nodes{nodes}", 1e6 / qps,
            f"qps={qps:.0f} efficiency={qps / (nodes * qps1):.2f} "
            f"model=ib_overlay"))
    if verbose:
        for r in rows:
            print(r)
    return rows
