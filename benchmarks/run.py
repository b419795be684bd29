"""Benchmark driver — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig16,tab2]
        [--smoke] [--out-dir bench-artifacts]

Prints ``name,us_per_call,derived`` CSV lines per artifact (plus section
headers). Exits NON-ZERO if any module raises or any embedded perf-claim
assertion (``common.check`` -> ClaimFailed) fails, so the CI bench-smoke
lane gates on the claims instead of letting a failed one scroll by.

--smoke (or env BENCH_SMOKE=1) caps stream durations / sweep widths /
timing iterations for CI; every claim assertion still runs.
--out-dir writes one ``BENCH_<tag>.json`` per module ({tag, module, ok,
error, rows, seconds, smoke}) for upload as a workflow artifact.

Modules:

    index_size      Table II   index footprint
    qps_recall      Fig 10/11  QPS + QPS/W vs recall frontier
    overfetch       Fig 15     EF sweep vs SymphonyQG-mode baseline
    scheduling      Fig 16     policy comparison (calibrated simulator)
    streaming       §IV-B      bucketed streaming scheduler vs per-shape
    overload        ISSUE 3/5  serving tiers (replicated/sharded/hybrid)
                               under 0.5x..8x offered load + retry storm
    tenancy         ISSUE 8    DWRR noisy-neighbor isolation + weighted
                               goodput (real topology + simulator overlay)
    breakdown       Fig 14     five-stage pipeline breakdown
    mulfree_bench   Fig 17/9   shift-add kernel time + recall delta
    pim_baselines   Fig 13     IVF-PQ recall ceiling vs PIMCQG
    multinode       Fig 18     sharded + hybrid scatter/gather + IB model
    pim_arch        Fig 19     PIM-HBM / AiM projection
    roofline_table  Fig 1 + §Roofline table from dry-run artifacts
    churn           ROADMAP 1  day-2 streaming mutation + autoscaling:
                               1% churn under 10x surge with zero
                               unavailability, <= 0.01 recall drift,
                               zero recompiles across live swaps
    placement       ROADMAP 2  heat-aware placement + hot-cluster
                               replication under Zipf(1.0) traffic:
                               >= 2x goodput vs byte-balanced at equal
                               recall, >= 1.5x hottest-shard heat-share
                               cut, zero-recompile drift rebalancing
                               (real topology + simulator overlay)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

MODULES = [
    ("tab2", "index_size"),
    ("fig10", "qps_recall"),
    ("fig15", "overfetch"),
    ("fig16", "scheduling"),
    ("stream", "streaming"),
    ("overload", "overload"),
    ("tenancy", "tenancy"),
    ("fig14", "breakdown"),
    ("fig17", "mulfree_bench"),
    ("fig13", "pim_baselines"),
    ("fig18", "multinode"),
    ("fig19", "pim_arch"),
    ("roofline", "roofline_table"),
    ("churn", "churn"),
    ("placement", "placement"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="cap workload sizes for CI (same as BENCH_SMOKE=1)")
    ap.add_argument("--out-dir", default=None,
                    help="write one BENCH_<tag>.json per module here")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        # must be set BEFORE benchmarks.common is imported by any module
        os.environ["BENCH_SMOKE"] = "1"
    # fig18's mesh-backend rows and all_gather calibration need a
    # multi-device host; force 8 virtual CPU devices BEFORE the first jax
    # import (single-device modules are unaffected — their arrays stay on
    # device 0). Respect a caller who already forced a count.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8").strip()
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    import importlib
    failures = []
    for tag, mod_name in MODULES:
        if only and tag not in only:
            continue
        print(f"# === {tag} ({mod_name}) ===", flush=True)
        t0 = time.time()
        rows, err = None, None
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
            rows = mod.run(verbose=True)
        except Exception as e:                              # noqa: BLE001
            failures.append((tag, repr(e)))
            err = repr(e)
            print(f"{tag},ERROR,{e!r}", flush=True)
        dt = time.time() - t0
        print(f"# {tag} done in {dt:.1f}s", flush=True)
        if args.out_dir:
            with open(os.path.join(args.out_dir, f"BENCH_{tag}.json"),
                      "w") as f:
                json.dump({"tag": tag, "module": mod_name,
                           "ok": err is None, "error": err,
                           "rows": rows, "seconds": round(dt, 2),
                           "smoke": os.environ.get("BENCH_SMOKE", "")
                           not in ("", "0")}, f, indent=1)
    if failures:
        print(f"# FAILURES: {failures}")
        sys.exit(1)
    print("# all benchmarks complete")


if __name__ == "__main__":
    main()
