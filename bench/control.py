#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --config sift1m --seeds 1,2,3 --queries 4096

Puts the reference in the program's place one precision step down (on a
float32 corpus its matmul in bfloat16, ``check.bf16_dot``; on a uint8 or
int8 corpus, where that matmul is exact, its distances rounded to
bfloat16, ``check.bf16_round``), answers the queries a run would have due
(pool queries in the seed's order), and judges those answers exactly as
``run.py`` judges the program's, in the configuration's dtype and metric.
It prints each number compared with its limit; ``dist_gap`` has to come
out over its limit on every seed. Not run by the benchmark's own runs: it
is how the limits were checked on the chip, and its test
(``test_bench_control.py``) runs it at a small size.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402


def control_verdict(cfg: dict, seed: int, n_queries: int) -> dict:
    """Judge the control's answers to ``n_queries`` due queries of seed
    ``seed`` against the exact reference."""
    from bench import arrivals, check, corpus

    key = corpus.seed_key(seed)
    mix = corpus.Mixture.from_config(cfg)
    metric = cfg["metric"]
    x = corpus.make_corpus(key, n=cfg["n"], dim=cfg["dim"], mix=mix)
    pool = np.asarray(corpus.make_queries(key, n=cfg["n_queries"],
                                          dim=cfg["dim"], mix=mix))
    order, _ = arrivals.stream({"arrivals": "at_once", "multiple": 1},
                               len(pool), seed, 1.0, rate_hint=n_queries)
    uniq, inv = np.unique(order, return_inverse=True)
    k = cfg["search"]["k"]
    ref, ref_d = corpus.exact_knn(pool[uniq], x, k, metric=metric)
    if mix.quantize is None:
        ids, dists = corpus.exact_knn(pool[uniq], x, k, metric=metric,
                                      dot=check.bf16_dot)
    else:
        ids, dists = ref, check.bf16_round(ref_d)
    ids, dists, ref = ids[inv], dists[inv], ref[inv]
    exact_d, scale = corpus.exact_dists(pool[order], ids, x, metric=metric)
    n = len(order)
    return check.judge(ids=ids, dists=dists.astype(np.float32),
                       answered=np.ones(n, bool), shed=np.zeros(n, bool),
                       ref_ids=ref, exact_d=exact_d, scale=scale,
                       n_corpus=cfg["n"], limits=cfg["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--queries", type=int, default=4096)
    args = ap.parse_args(argv)
    from bench import run
    cfg = run.load_config(args.config)
    for seed in (int(s) for s in args.seeds.split(",")):
        v = control_verdict(cfg, seed, args.queries)
        print(json.dumps({"config": args.config, "seed": seed,
                          "correct": v["correct"], "checks": v["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
