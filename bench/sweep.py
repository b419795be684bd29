#!/usr/bin/env python3
"""Find a configuration's operating point and its knee, on the chip.

    python3 bench/sweep.py --config sift1m --seed 0 \
        --efs 16,24,32,40,48 --nprobes 4,16 --rates 0.6,0.8,0.9,1.0,1.1

A helper, not a workload. After one set-up (corpus, query pool, index,
and the exact top-10 of the whole pool), it serves the configuration:

- at each ``ef`` of ``--efs`` with the configuration's ``nprobe``, and at
  each ``nprobe`` of ``--nprobes`` with the configuration's ``ef``: a
  batch of pool queries, all due at t=0, through a one-bucket (64)
  topology; it prints recall@10 and the batch rate;
- at each rate of ``--rates`` (a multiple of the batch rate at the
  operating point, the smallest ef whose recall reaches ``--target``,
  0.91: the 0.90 the check holds every seed to, and room for seeds):
  an open-loop Poisson stream through the configuration's own topology;
  it prints the shed count, p50/p99, and the backlog's growth: the slope
  of latency against arrival time, and the median latency of the last
  quarter of arrivals over that of the first.

A rate is sustained when nothing is shed and the backlog does not grow;
the knee is the highest such rate. ``--dump-trace PATH`` records a short
device trace of the batch at the operating point into PATH (gzipped
``trace.Event`` rows; the test fixture is one) and prints its planes,
lines and busiest ops. ``--out`` writes every reading as JSON.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402


def log(msg):
    print(msg, flush=True)


def recall(ids, truth, k=10):
    return float(np.mean([len(np.intersect1d(a[:k], b[:k])) / k
                          for a, b in zip(ids, truth)]))


def variant(eng, **search):
    """The same index under another SearchConfig (no rebuild)."""
    from repro.core import engine
    return engine.PIMCQGEngine(eng.index, eng.host, eng.place, eng.icfg,
                               dataclasses.replace(eng.scfg, **search))


def batch(eng, pool, truth, n):
    from repro.core.topology import TopologyConfig
    topo = TopologyConfig(buckets=(64,), admission_depth=None).build(eng)
    topo.warm()
    topo.run(pool[:256], np.zeros(256))            # host path warm
    t = time.perf_counter()
    rep = topo.run(pool[:n], np.zeros(n))
    dt = time.perf_counter() - t
    return {"recall": recall(np.asarray(rep.ids), truth[:n]),
            "qps": (n - rep.n_shed) / dt, "seconds": dt}


def open_loop(topo, pool, rate, seconds, seed):
    from bench import arrivals
    order, arr = arrivals.stream({"arrivals": "poisson", "rate_qps": rate},
                                 len(pool), seed, seconds)
    rep = topo.run(pool[order], arr)
    lat = np.asarray(rep.latency_s)
    ok = ~np.isnan(lat)
    slope = float(np.polyfit(arr[ok], lat[ok], 1)[0]) if ok.sum() > 2 \
        else float("nan")
    q = len(arr) // 4
    first, last = lat[:q][ok[:q]], lat[-q:][ok[-q:]]
    growth = float(np.median(last) / np.median(first)) \
        if len(first) and len(last) else float("nan")
    s = np.sort(np.where(ok, lat, np.inf))
    return {"rate": rate, "n": len(arr), "shed": int(rep.n_shed),
            "p50_ms": float(s[len(s) // 2]) * 1e3,
            "p99_ms": float(s[int(np.ceil(0.99 * len(s))) - 1]) * 1e3,
            "slope_ms_per_s": slope * 1e3, "last_over_first": growth,
            "flushes": rep.n_flushes,
            "mean_flush": float(np.mean(rep.flush_sizes))}


def dump_trace(eng, pool, path: Path):
    from bench import trace
    from repro.core.topology import TopologyConfig
    topo = TopologyConfig(buckets=(64,), admission_depth=None).build(eng)
    topo.warm()
    tracer = trace.WindowTracer(0.5, 1.0)
    n = min(1024, len(pool))
    topo.run(pool[:n], np.zeros(n), ticker=tracer)
    tracer.close()
    events = tracer.events()
    trace.save_events(events, path)
    lines = {}
    for e in events:
        lines.setdefault((e.plane, e.line), []).append(e)
    for (plane, line), evs in sorted(lines.items()):
        log(f"trace: {plane} | {line}: {len(evs)} events; first "
            f"{evs[0].name!r}")
    ops = trace.device_ops(events)
    span = trace.window_ns(events)
    for plane, v in ops.items():
        log(f"trace: {plane} busy {trace.busy_ns(v, *span) * 1e-9:.6f} s "
            f"of {(span[1] - span[0]) * 1e-9:.6f} s; top "
            f"{trace.top_ops(v, 15)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--efs", default="")
    ap.add_argument("--nprobes", default="")
    ap.add_argument("--batch-queries", type=int, default=2048)
    ap.add_argument("--target", type=float, default=0.91)
    ap.add_argument("--rates", default="",
                    help="multiples of the operating point's batch rate")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--generator", default="",
                    help="generator overrides, key=value,...")
    ap.add_argument("--dump-trace", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    if jax.devices()[0].platform != "tpu":
        print("bench/sweep.py: needs a TPU", file=sys.stderr)
        return 1
    from bench import corpus, run
    run.enable_compile_cache()
    cfg = run.load_config(args.config)
    for kv in filter(None, args.generator.split(",")):
        k, v = kv.split("=")
        cfg["generator"][k] = type(cfg["generator"][k])(v)
    log(f"config {args.config}, generator {cfg['generator']}, seed "
        f"{args.seed}")
    eng, pool, _ = run.setup_index(cfg, args.seed, log)
    t = time.perf_counter()
    x = corpus.make_corpus(corpus.seed_key(args.seed), n=cfg["n"],
                           dim=cfg["dim"], mix=corpus.Mixture.from_config(cfg))
    nb = min(args.batch_queries, len(pool))
    truth, _ = corpus.exact_knn(pool[:nb], x, cfg["k"], metric=cfg["metric"])
    del x
    log(f"reference of {nb} queries in {time.perf_counter() - t:.3f} s")
    out = {"config": args.config, "seed": args.seed,
           "generator": cfg["generator"], "budget": eng.index.budget,
           "ef": [], "nprobe": [], "rates": []}

    scfg = eng.scfg
    best = None
    for ef in [int(e) for e in filter(None, args.efs.split(","))]:
        r = batch(variant(eng, ef=ef), pool, truth, nb)
        r["ef"] = ef
        out["ef"].append(r)
        log(f"nprobe {scfg.nprobe} ef {ef}: recall@10 {r['recall']:.4f}, "
            f"{r['qps']:.1f} queries/s")
        if best is None and r["recall"] >= args.target:
            best = r
    for npb in [int(p) for p in filter(None, args.nprobes.split(","))]:
        r = batch(variant(eng, nprobe=npb), pool, truth, nb)
        r["nprobe"] = npb
        out["nprobe"].append(r)
        log(f"nprobe {npb} ef {scfg.ef}: recall@10 {r['recall']:.4f}, "
            f"{r['qps']:.1f} queries/s")
    op = variant(eng, ef=best["ef"]) if best else eng
    out["operating_ef"] = best["ef"] if best else None
    log(f"operating point: nprobe {op.scfg.nprobe}, ef {op.scfg.ef}")

    if args.dump_trace:
        dump_trace(op, pool, Path(args.dump_trace))
    if args.rates:
        base = best["qps"] if best else batch(op, pool, truth, nb)["qps"]
        topo = run.topology_config(cfg, {}).build(op)
        topo.warm()
        open_loop(topo, pool, 0.5 * base, 1.0, args.seed + 1)
        for i, f in enumerate(float(f) for f in args.rates.split(",")):
            r = open_loop(topo, pool, f * base, args.seconds,
                          args.seed + 2 + i)
            r["fraction"] = f
            out["rates"].append(r)
            log(f"rate {r['rate']:.1f} ({f} x batch): {r['n']} queries, "
                f"shed {r['shed']}, p50 {r['p50_ms']:.1f} ms, p99 "
                f"{r['p99_ms']:.1f} ms, backlog slope "
                f"{r['slope_ms_per_s']:.2f} ms/s, last/first quarter "
                f"{r['last_over_first']:.2f}, mean flush "
                f"{r['mean_flush']:.1f}")
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
