"""Smoke run of the graph-ANN serving path on a TPU.

    python chip_smoke.py [--seed S]              # one chip
    python chip_smoke.py --chips 4 [--seed S]    # the 2x2 sharded path

One chip. A corpus of the BIGANN/SIFT1M shape (the SIFT row of
ANN-Benchmarks and big-ann-benchmarks: 1,000,000 x 128 float32, L2, k=10)
is drawn from ``--seed`` with ``data.synthetic.clustered_vectors``, one
Gaussian blob per IVF cluster as ``benchmarks/common.py`` draws its. The
index is built on the chip through ``PIMCQGEngine.build`` (1024 clusters,
degree 32, knn_k 64, k-means trained on a 100,000-row sample), and a few
hundred queries are served through ``TopologyConfig(buckets=(64,))
.build(eng).run`` with the default ``SearchConfig`` (nprobe=8, ef=40,
mulfree), which puts the ``topk_select`` kernel on the rerank path. Before
that, the four Pallas kernels are compiled for the device and checked
bitwise against their refs at serving widths.

Four chips (``--chips 4``). The same corpus and index, served by
``TopologyConfig(shards=4, exec="mesh")`` on a 4-device mesh and by
``exec="inproc"``; the mesh ids must equal the inproc ids, the dists must
agree, and the stacked index must sit on 4 distinct devices.

Both runs fail on recall@10 below ``recall_floor`` against a brute-force
float32 numpy reference, on a ``-1`` id in any admitted row, on any shed
query, or on an XLA compile inside the served window after ``warm()``.
The latencies printed come from a single smoke run, not a benchmark. The
last line of stdout is one JSON object naming the device. Without a TPU
the script exits non-zero and prints no such line.

Nothing is cut from the configuration above.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core import compact_index, engine
from ..core.topology import TopologyConfig
from ..data import synthetic
from ..kernels import binary_ip, ref, topk_select
from .compile_cache import enable_compile_cache

__all__ = ["SmokeConfig", "SmokeFailed", "CompileCounter", "smoke_one_chip",
           "smoke_mesh", "check_kernels", "main"]

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    n: int = 1_000_000
    dim: int = 128
    n_clusters: int = 1024      # IVF clusters, and the corpus's Gaussian blobs
    degree: int = 32
    knn_k: int = 64
    kmeans_sample: int = 100_000
    n_queries: int = 256
    bucket: int = 64
    k: int = 10
    recall_floor: float = 0.6
    seed: int = 0

    def index_config(self) -> compact_index.IndexConfig:
        return compact_index.IndexConfig(
            dim=self.dim, n_clusters=self.n_clusters, degree=self.degree,
            knn_k=self.knn_k, kmeans_sample=self.kmeans_sample)


class SmokeFailed(AssertionError):
    """A smoke-run check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailed(msg)


class CompileCounter:
    """Counts XLA backend compiles (and their seconds) while entered and
    armed; ``arm`` doubles as a ``ServingTopology.run`` ticker, so a counter
    entered unarmed around ``run`` counts from the stream clock's start."""

    def __init__(self, armed: bool = True):
        self.armed = armed
        self.count = 0
        self.seconds = 0.0

    def arm(self, _t=None):
        self.armed = True

    def _on_event(self, event: str, duration: float, **_):
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def check_kernels(seed: int, log=print) -> None:
    """Each Pallas kernel, compiled for the device (interpret mode off a
    TPU), against its ref at serving widths: bitwise."""
    rng = np.random.default_rng(seed)
    interp = jax.default_backend() != "tpu"

    def same(name, got, want):
        for g, w in zip(got, want):
            check(np.array_equal(np.asarray(g), np.asarray(w)),
                  f"kernel {name} differs from its ref on this device")
        log(f"kernel {name}: matches its ref bitwise")

    # rerank selection: Q=64 rows of nprobe*ef=320 candidates, with pads
    # and duplicate ids
    ids = jnp.asarray(rng.integers(-1, 400, (64, 320)), jnp.int32)
    d = jnp.asarray(rng.standard_normal((64, 320)), jnp.float32)
    same("topk_select C=320",
         topk_select.topk_select(ids, d, k=10, interpret=interp),
         ref.topk_select_ref(ids, d, k=10))
    # sharded-origin merge: 4 sorted runs of 10 with disjoint ids
    runs = np.sort(rng.standard_normal((64, 4, 10)), axis=-1)
    d = jnp.asarray(runs.reshape(64, 40), jnp.float32)
    ids = jnp.asarray(np.arange(64 * 40).reshape(64, 40), jnp.int32)
    same("merge_topk 4x10",
         topk_select.merge_topk(ids, d, k=10, interpret=interp),
         ref.merge_topk_ref(ids, d, k=10))
    # mulfree ranking over a 2048-node block of 128-d codes
    codes = jnp.asarray(rng.integers(0, 256, (2048, 16)), jnp.uint8)
    f_add = jnp.asarray(rng.integers(0, 1 << 20, (2048,)), jnp.int32)
    lut = jnp.asarray(rng.integers(-4096, 4096, (128,)), jnp.int32)
    scal = (jnp.int32(int(lut.sum())), jnp.int32(2), jnp.int32(5))
    same("binary_ip_rank M=2048",
         [binary_ip.binary_ip_rank(codes, f_add, lut, *scal, dim=128,
                                   interpret=interp)],
         [ref.binary_ip_rank_ref(codes, f_add, lut, *scal, 128)])
    nv = jnp.int32(2000)
    same("cluster_scan ef=40",
         binary_ip.cluster_scan(codes, f_add, lut, *scal, nv, dim=128,
                                ef=40, interpret=interp),
         ref.cluster_scan_ref(codes, f_add, lut, *scal, 128, 40, nv))


def make_corpus(cfg: SmokeConfig) -> tuple[np.ndarray, np.ndarray]:
    x, _ = synthetic.clustered_vectors(cfg.seed, cfg.n, cfg.dim,
                                       cfg.n_clusters)
    return x, synthetic.query_set(cfg.seed, x, cfg.n_queries)


def recall_at_k(ids: np.ndarray, truth: np.ndarray, k: int) -> float:
    hits = sum(len(set(a[:k].tolist()) & set(b[:k].tolist()))
               for a, b in zip(ids, truth))
    return hits / (len(ids) * k)


def build_engine(cfg: SmokeConfig, x: np.ndarray) -> engine.PIMCQGEngine:
    eng = engine.PIMCQGEngine.build(
        jax.random.PRNGKey(cfg.seed), x, cfg.index_config(),
        engine.SearchConfig(k=cfg.k), buckets=(cfg.bucket,))
    jax.block_until_ready((eng.placed, eng.host.vectors))
    return eng


def _serve(topo, queries, label: str, log):
    """warm() every executable, then serve the queries, counting the XLA
    compiles inside the served window: from the stream clock's start (a
    sharded run first routes the whole stream, before its clock starts)."""
    t0 = time.perf_counter()
    n_exec = topo.warm()
    warm_s = time.perf_counter() - t0
    with CompileCounter() as whole, CompileCounter(armed=False) as window:
        rep = topo.run(queries, ticker=window.arm)
    log(f"{label}: warm() built {n_exec} serving executable(s) in "
        f"{warm_s:.3f} s; {whole.count - window.count} XLA compiles in "
        f"run() before the stream clock started")
    return rep, window.count


def _check_served(rep, truth, cfg: SmokeConfig, in_window: int, label: str,
                  log) -> float:
    admitted = ~rep.shed
    neg_rows = int((rep.ids[admitted] < 0).any(axis=1).sum())
    recall = recall_at_k(rep.ids, truth, cfg.k)
    log(f"{label}: recall@{cfg.k} {recall:.4f} over {rep.n_queries} queries "
        f"(floor {cfg.recall_floor}); shed {rep.n_shed}; rows with -1 ids "
        f"{neg_rows}; compiles in served window {in_window}; "
        f"{rep.n_flushes} flushes")
    log(f"{label}: p50 {rep.p50_ms:.3f} ms, p99 {rep.p99_ms:.3f} ms "
        f"(one smoke run, not a benchmark)")
    check(rep.n_shed == 0, f"{label}: {rep.n_shed} queries shed")
    check(neg_rows == 0, f"{label}: {neg_rows} admitted rows hold -1 ids")
    check(in_window == 0,
          f"{label}: {in_window} XLA compiles inside the served window")
    check(recall >= cfg.recall_floor,
          f"{label}: recall@{cfg.k} {recall:.4f} < floor {cfg.recall_floor}")
    return recall


def _setup(cfg: SmokeConfig, log):
    t0 = time.perf_counter()
    x, q = make_corpus(cfg)
    truth = synthetic.ground_truth(x, q, cfg.k)
    log(f"corpus: {x.shape[0]:,} x {x.shape[1]} {x.dtype}, L2, "
        f"k={cfg.k}, {len(q)} queries, seed {cfg.seed} "
        f"(made with its exact top-{cfg.k} in "
        f"{time.perf_counter() - t0:.3f} s)")
    t0 = time.perf_counter()
    eng = build_engine(cfg, x)
    icfg = cfg.index_config()
    log(f"index: built on {jax.devices()[0].platform} in "
        f"{time.perf_counter() - t0:.3f} s; {eng.index.n_clusters} clusters "
        f"x budget {eng.index.budget}, degree {icfg.degree}, "
        f"knn_k {icfg.knn_k}, k-means sample {icfg.kmeans_sample}")
    return q, truth, eng


def _peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return f"{stats['peak_bytes_in_use']:,} B"


def smoke_one_chip(cfg: SmokeConfig, log=print) -> dict:
    """Build the index and serve ``cfg.n_queries`` through a one-engine
    topology; raises SmokeFailed when a check fails."""
    with CompileCounter() as total:
        check_kernels(cfg.seed, log)
        q, truth, eng = _setup(cfg, log)
        topo = TopologyConfig(buckets=(cfg.bucket,)).build(eng)
        rep, in_window = _serve(topo, q, "served", log)
    # the serving executable warm() built, compiled once more to read it
    fn = eng._search_cache[cfg.bucket]
    hlo = fn.lower(eng.placed, eng.index.centroids, eng.index.rotation,
                   eng.host.vectors, jnp.zeros((cfg.bucket, cfg.dim)),
                   jnp.int32(cfg.bucket)).compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"compile: {total.count} XLA compiles, {total.seconds:.3f} s in "
        f"the backend compiler")
    log(f"serving executable holds tpu_custom_call: {has_kernel}")
    log(f"peak device bytes: {_peak_bytes()}")
    recall = _check_served(rep, truth, cfg, in_window, "served", log)
    if jax.default_backend() == "tpu":
        check(has_kernel, "no Pallas kernel in the serving executable")
    return {"recall": recall, "p50_ms": rep.p50_ms, "p99_ms": rep.p99_ms,
            "tpu_custom_call": has_kernel}


def smoke_mesh(cfg: SmokeConfig, n_shards: int = 4, log=print) -> dict:
    """Serve the same queries through ``n_shards`` shards on a device mesh
    and in-process; raises SmokeFailed when they disagree."""
    check(len(jax.devices()) >= n_shards,
          f"the mesh path needs {n_shards} devices, JAX sees "
          f"{len(jax.devices())}")
    q, truth, eng = _setup(cfg, log)
    topos = {ex: TopologyConfig(shards=n_shards, exec=ex,
                                buckets=(cfg.bucket,)).build(eng)
             for ex in ("mesh", "inproc")}
    placed = topos["mesh"]._exec._placed       # the stacked shard index
    devices = set().union(*(leaf.sharding.device_set
                            for leaf in jax.tree.leaves(placed)))
    log(f"mesh: stacked index on {len(devices)} devices: "
        f"{sorted(d.id for d in devices)}")
    check(len(devices) == n_shards,
          f"stacked index spans {len(devices)} devices, not {n_shards}")
    reps = {}
    for ex, topo in topos.items():
        rep, in_window = _serve(topo, q, ex, log)
        _check_served(rep, truth, cfg, in_window, ex, log)
        reps[ex] = rep
    same_ids = np.array_equal(reps["mesh"].ids, reps["inproc"].ids)
    close = np.allclose(reps["mesh"].dists, reps["inproc"].dists)
    log(f"mesh vs inproc: ids identical {same_ids}; dists allclose {close}")
    check(same_ids, "mesh ids differ from inproc ids")
    check(close, "mesh dists differ from inproc dists")
    return {"devices": len(devices), "same_ids": same_ids}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the serving path on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded mesh-vs-inproc path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()

    def log(msg):
        print(msg, flush=True)

    log(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{cache}")
    cfg = SmokeConfig(seed=args.seed)
    if args.chips == 4:
        smoke_mesh(cfg, 4, log)
    else:
        smoke_one_chip(cfg, log)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0
