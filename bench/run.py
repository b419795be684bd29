#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in ``BENCHMARK.json`` at the checkout root; its
configuration (``bench/configs/<config>.json``), traffic mix
(``bench/traffic/<mix>.json``) and metric readers
(``bench/metrics/<metric>.py``) are found by name. The run:

1. set-up: draws the corpus and the query pool from the seed on the
   device, in the configuration's ``dtype``, builds the index
   (``PIMCQGEngine.build``), builds the topology
   (``TopologyConfig(...).build``), compiles it (``ServingTopology.warm``)
   and serves a warm-up stream; ``setup_s`` runs from process start to the
   window's start;
2. window: one ``ServingTopology.run`` call over the mix's stream. With
   ``--trace 1``, after the window, the profiler records 3 s of a second,
   shorter stream of the same mix (it slows the host, so it stays out of
   the window), and the hop counts of full 64-query flushes of pool
   queries are read from ``PIMCQGEngine.search``;
3. check: the program's state is freed, the corpus is drawn again and
   every answer due in the window is compared with the brute-force
   reference in the configuration's ``metric`` (``check.py``). The
   program is not told the metric: one that ranks by another fails.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit,
also printed as the last lines on stderr. Without a TPU, or with fewer
chips than the cell asks for, it exits 1 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

LOCKSTEP_FLUSHES = 8          # 64-query flushes whose hops are counted
TRACE_SECONDS = 3.0           # traced span of the stream after the window


@dataclasses.dataclass
class Context:
    """What a metric reader may read; fields are None where not taken."""
    config: dict               # the configuration's file
    peaks: dict                # bench/peaks.json row of this device
    setup_s: float
    window_s: float            # host clock around the window's run()
    answered: np.ndarray       # (due,) bool, per query due in the window
    latency_s: np.ndarray      # (due,) from the scheduled arrival; NaN = none
    good: np.ndarray           # (due,) answered and no row fault
    recall: float
    flush_sizes: list
    buckets: tuple
    events: list | None = None     # trace.Event list of the traced span
    hops: np.ndarray | None = None  # (flushes, S, L) SearchStats.hops


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_config(name: str) -> dict:
    """``bench/configs/<name>.json``, refused where its ``dtype``,
    ``metric`` or ``generator`` is not one the harness takes."""
    from bench import corpus
    cfg = load_json(BENCH / "configs" / f"{name}.json")
    corpus.Mixture.from_config(cfg)
    return cfg


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device_kind {kind!r} in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[kind]


def load_reader(name: str):
    """``bench/metrics/<name>.py``, else the reader of the quantity the
    name splits (``idle_share.poisson`` -> ``idle_share.py``)."""
    for stem in (name, name.split(".")[0]):
        path = BENCH / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                "bench_metric_" + stem.replace(".", "_"), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"bench/metrics/")


def metrics_of(entries: list, cell: str, ctx: Context) -> dict:
    out = {}
    for m in entries:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def enable_compile_cache() -> str:
    """JAX's persistent cache: $JAX_COMPILATION_CACHE_DIR where set, else
    ``.jax_cache/`` at the checkout root (a fixed path: the directory is
    part of what an entry is found by)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """XLA backend compiles while armed (armed from the first tick of the
    window's run, so it counts what compiles inside the window)."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed = False
        self.count = 0

    def _on(self, event: str, duration: float, **_):
        if self.armed and event == self.EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def setup_index(cfg: dict, seed: int, log):
    """Corpus and query pool from the seed, in the configuration's dtype,
    then the program's index. Returns (engine, pool on the host, corpus
    rows N)."""
    import jax
    from repro.core import compact_index, engine
    from bench import corpus

    key = corpus.seed_key(seed)
    mix = corpus.Mixture.from_config(cfg)
    t = time.perf_counter()
    x = corpus.make_corpus(key, n=cfg["n"], dim=cfg["dim"], mix=mix)
    pool = np.asarray(corpus.make_queries(key, n=cfg["n_queries"],
                                          dim=cfg["dim"], mix=mix))
    x.block_until_ready()
    t_made = time.perf_counter() - t
    x_host = np.asarray(x)
    del x                                   # the program keeps its own copy
    log(f"data: {cfg['n']:,} x {cfg['dim']} {mix.dtype} corpus and "
        f"{len(pool):,} queries made on the device in {t_made:.3f} s, "
        f"copied to the host in {time.perf_counter() - t - t_made:.3f} s")
    t = time.perf_counter()
    eng = engine.PIMCQGEngine.build(
        jax.random.fold_in(key, 2), x_host,
        compact_index.IndexConfig(**cfg["index"]),
        engine.SearchConfig(**cfg["search"]))
    jax.block_until_ready((eng.placed, eng.host.vectors))
    log(f"build: {eng.index.n_clusters} clusters x budget "
        f"{eng.index.budget} in {time.perf_counter() - t:.3f} s")
    return eng, pool, x_host.shape[0]


def steady_rate(latency_s, arrivals) -> float | None:
    """Queries completed per second between the first and the last
    completion of a stream (the first completions only fill the pipe);
    None where the stream is too short to tell."""
    done = np.sort((np.asarray(arrivals) + np.asarray(latency_s))[
        ~np.isnan(latency_s)])
    if not len(done):
        return None
    first = np.searchsorted(done, done[0], side="right")
    span = done[-1] - done[0]
    return (len(done) - first) / span if span > 0 else None


def topology_config(cfg: dict, mix: dict):
    from repro.core.topology import TopologyConfig
    kw = {**cfg.get("topology", {}), **mix.get("topology", {})}
    if kw.get("buckets") is not None:
        kw["buckets"] = tuple(kw["buckets"])
    return TopologyConfig(**kw)


def reference(cfg: dict, seed: int, pool: np.ndarray, order: np.ndarray,
              ids: np.ndarray, rows: np.ndarray, log):
    """The exact top-k of every due query, and the exact distance and
    scale of every id returned in ``rows``, in the configuration's metric;
    from a fresh draw of the corpus (nothing the program made is read)."""
    from bench import corpus
    t = time.perf_counter()
    metric = cfg["metric"]
    x = corpus.make_corpus(corpus.seed_key(seed), n=cfg["n"], dim=cfg["dim"],
                           mix=corpus.Mixture.from_config(cfg))
    uniq, inv = np.unique(order, return_inverse=True)
    ref_u, _ = corpus.exact_knn(pool[uniq], x, cfg["search"]["k"],
                                metric=metric)
    exact_d = np.zeros(ids.shape)
    scale = np.ones(ids.shape)
    if len(rows):
        d, s = corpus.exact_dists(pool[order[rows]], ids[rows], x,
                                  metric=metric)
        exact_d[rows], scale[rows] = d, s
    del x
    log(f"reference: exact top-{cfg['search']['k']} of {len(uniq):,} "
        f"distinct queries in {time.perf_counter() - t:.3f} s")
    return ref_u[inv], exact_d, scale


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, log=print, t_start=None) -> dict:
    """Set up, serve the window, check; the result dict of one run."""
    import jax
    from bench import arrivals, check
    from bench import trace as trace_mod

    t_start = T_START if t_start is None else t_start
    devices = jax.devices()[:cell["chips"]]
    eng, pool, n_corpus = setup_index(cfg, seed, log)

    t = time.perf_counter()
    topo = topology_config(cfg, mix).build(eng)
    n_exec = topo.warm()
    log(f"warm: {n_exec} serving executable(s) for buckets {topo.buckets} "
        f"in {time.perf_counter() - t:.3f} s")
    w_order, w_arr = arrivals.warmup_stream(mix, len(pool), seed)
    t = time.perf_counter()
    w_rep = topo.run(pool[w_order], w_arr)
    w_s = time.perf_counter() - t
    rate = steady_rate(w_rep.latency_s, w_arr)
    log(f"warm-up stream: {w_rep.n_queries} queries in {w_s:.3f} s "
        f"({rate} queries/s once the first flush was out), "
        f"{w_rep.n_shed} shed")

    order, arr = arrivals.stream(mix, len(pool), seed, seconds,
                                 rate_hint=rate)
    queries = pool[order]
    with CompileCounter() as compiles:
        def ticker(t):
            compiles.armed = True
        # set-up's objects are frozen out of the collector's reach, so that
        # a collection inside the window does not walk them
        gc.collect()
        gc.freeze()
        setup_s = time.perf_counter() - t_start
        t0 = time.perf_counter()
        rep = topo.run(queries, arr, ticker=ticker)
        window_s = time.perf_counter() - t0
    log(f"window: {len(order):,} queries due, {rep.n_flushes} flushes, "
        f"{window_s:.3f} s, {rep.n_shed} shed, {compiles.count} XLA "
        f"compiles inside the window")
    peak = memory_peak_bytes(devices)

    hops = events = None
    if trace:
        # the profiler slows the host, so it records a stream of its own
        # after the window: the window and its checks stay as untraced
        t_order, t_arr = arrivals.stream(mix, len(pool), seed,
                                         TRACE_SECONDS + 1.0, rate_hint=rate)
        tracer = trace_mod.WindowTracer(0.5, 0.5 + TRACE_SECONDS)
        t = time.perf_counter()
        topo.run(pool[t_order], t_arr, ticker=tracer)
        tracer.close()
        events = tracer.events()
        log(f"trace: a stream of {len(t_order):,} queries after the window, "
            f"{TRACE_SECONDS} s of it traced, in "
            f"{time.perf_counter() - t:.3f} s")
        flushes = []
        for f in range(min(LOCKSTEP_FLUSHES, len(pool) // 64)):
            _, stats = eng.search(pool[f * 64:(f + 1) * 64], pad_to=64)
            flushes.append(np.asarray(stats.hops))
        hops = np.stack(flushes) if flushes else None

    ids, dists = np.asarray(rep.ids), np.asarray(rep.dists)
    shed = np.asarray(rep.shed, bool)
    lat = np.asarray(rep.latency_s, float)
    answered = ~shed & ~np.isnan(lat)
    flush_sizes, buckets = list(rep.flush_sizes), tuple(topo.buckets)
    del topo, eng, rep, w_rep
    gc.collect()

    rows = np.flatnonzero(answered)
    ok_rows = rows[~check.row_faults(ids[rows], dists[rows], n_corpus)]
    ref_ids, exact_d, scale = reference(cfg, seed, pool, order, ids,
                                        ok_rows, log)
    verdict = check.judge(ids=ids, dists=dists, answered=answered,
                          shed=shed, ref_ids=ref_ids, exact_d=exact_d,
                          scale=scale, n_corpus=n_corpus,
                          limits=cfg["limits"])
    good = np.zeros(len(order), bool)
    good[ok_rows] = True

    dev = devices[0]
    ctx = Context(config=cfg, peaks=peaks_for(dev.device_kind)
                  if dev.platform == "tpu" else {},
                  setup_s=setup_s, window_s=window_s, answered=answered,
                  latency_s=lat, good=good, recall=verdict["recall"],
                  flush_sizes=flush_sizes, buckets=buckets, events=events,
                  hops=hops)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": verdict["correct"], "attempted": len(order),
           "failed": int(len(order) - answered.sum())}
    if trace:
        out["metrics"] = metrics_of(bench["per_layer"], cell["name"], ctx)
        lo_hi = trace_mod.window_ns(events or [])
        ops = trace_mod.device_ops(events or [])
        if lo_hi:
            lo, hi = lo_hi
            busy = [trace_mod.busy_ns(v, lo, hi) for v in ops.values()]
            device["busy_s"] = float(np.mean(busy)) * 1e-9 if busy else 0.0
            device["window_s"] = (hi - lo) * 1e-9
            first = next(iter(ops.values()), [])
            out["breakdown"] = {
                "device_ops": trace_mod.top_ops(first),
                "idle_gaps": trace_mod.idle_gaps(first, lo, hi)}
    else:
        out["metrics"] = metrics_of(bench["end_to_end"], cell["name"], ctx)
    out["device"] = device
    out["checks"] = verdict["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        return 2
    cell = cells[args.workload]

    import jax
    import repro.core.topology  # noqa: F401  (no program, no run)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench/run.py: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devs)} {devs[0].platform} "
              f"device(s)", file=sys.stderr)
        return 1
    cache = enable_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    log(f"device: {devs[0].device_kind} x {cell['chips']}; compile cache "
        f"{cache}")
    cfg = load_config(cell["config"])
    from bench import arrivals
    mix = arrivals.load_mix(BENCH / "traffic" / f"{cell['traffic']}.json")
    out = run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                   bool(args.trace), log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} {c['must']} {c['limit']!r}")
    log(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
