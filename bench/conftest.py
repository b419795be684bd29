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
    "metric": "l2", "dtype": "float32",
    "index": {"dim": 16, "n_clusters": 16, "degree": 8, "knn_k": 16,
              "kmeans_sample": 0},
    "search": {"nprobe": 4, "ef": 16, "k": 10},
    "topology": {},
    "generator": {"components": 4, "rank": 4, "center_scale": 0.5,
                  "noise": 0.1},
}


# quantize of the tiny integer configurations: values about 127 +- 29
# (uint8) and -1 +- 29 (int8), of which about 7e-5 are clipped
QUANTIZE = {"uint8": {"scale": 40.0, "offset": 128.0},
            "int8": {"scale": 40.0, "offset": 0.0}}


def tiny_config(dtype: str = "float32", metric: str = "l2") -> dict:
    """The tiny configuration in ``dtype`` and ``metric``, held to sift1m's
    limits except recall (a 16-cluster index of 4,096 rows reaches about
    0.6) and, on an integer corpus, ``dist_gap``: there the program's
    distances and the reference's are both exact integers (program 0.0 on
    the CPU; the bfloat16 control 5.1e-5-5.5e-5 for uint8, 4.6e-4-1.0e-3
    for int8), so the comparison is exact, limit 0."""
    cfg = copy.deepcopy(TINY)
    cfg["dtype"], cfg["metric"] = dtype, metric
    sift = json.loads((BENCH / "configs" / "sift1m.json").read_text())
    cfg["limits"] = dict(sift["limits"], recall=0.5)
    if dtype in QUANTIZE:
        cfg["generator"]["quantize"] = dict(QUANTIZE[dtype])
        cfg["limits"]["dist_gap"] = 0.0
    return cfg


@pytest.fixture
def tiny_cfg():
    """The tiny float32 L2 configuration (``tiny_config``)."""
    return tiny_config()


@pytest.fixture
def bench_json():
    return json.loads((BENCH.parent / "BENCHMARK.json").read_text())
