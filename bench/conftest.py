"""Fixtures of the benchmark's own CPU tests: a tiny configuration of the
same shape as ``configs/*.json``, small enough for a test run."""

import copy
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
for _p in (str(BENCH.parent / "src"), str(BENCH.parent)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TINY = {
    "n": 4096, "dim": 16, "n_queries": 512, "k": 10,
    "index": {"dim": 16, "n_clusters": 16, "degree": 8, "knn_k": 16,
              "kmeans_sample": 0},
    "search": {"nprobe": 4, "ef": 16, "k": 10},
    "topology": {},
    "generator": {"components": 4, "rank": 4, "center_scale": 0.5,
                  "noise": 0.1},
}


@pytest.fixture
def tiny_cfg():
    """The tiny configuration, held to sift1m's limits except recall (a
    16-cluster index of 4,096 rows reaches about 0.6)."""
    cfg = copy.deepcopy(TINY)
    sift = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    cfg["limits"] = dict(sift["limits"], recall=0.5)
    return cfg


@pytest.fixture
def bench_json():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())
