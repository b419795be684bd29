"""The control comes out as not correct: the reference in the program's
place with its matmul in bfloat16, one step below the configuration's
float32, judged as a run judges the program (small size; on the chip it
runs at the cells' sizes, PERF.md)."""

import pytest

from bench import control
from bench.conftest import tiny_config


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_bfloat16_control_fails_dist_gap(tiny_cfg, seed):
    v = control.control_verdict(tiny_cfg, seed, 256)
    checks = v["checks"]
    assert not v["correct"]
    assert checks["dist_gap"]["value"] > 3 * checks["dist_gap"]["limit"]
    # it fails on precision alone: every answer came, well formed, and
    # near-exact neighbours
    assert checks["lost"]["value"] == 0 and checks["bad_rows"]["value"] == 0
    assert checks["recall"]["value"] >= checks["recall"]["limit"]


def test_float32_reference_in_the_same_place_passes(tiny_cfg, monkeypatch):
    from bench import check, corpus
    monkeypatch.setattr(check, "bf16_dot", corpus.highest_dot)
    v = control.control_verdict(tiny_cfg, 1, 256)
    assert v["correct"], v["checks"]


@pytest.mark.parametrize("dtype,metric,seed", [
    ("uint8", "l2", 1), ("uint8", "l2", 2), ("uint8", "l2", 2**31 + 3),
    ("int8", "l2", 1), ("float32", "ip", 1), ("float32", "ip", 2**31 + 3)])
def test_control_fails_dist_gap_in_its_dtype_and_metric(dtype, metric, seed):
    """On a uint8 or int8 corpus the control is the exact answer with its
    distances rounded to bfloat16 (a bfloat16 matmul is exact there); on
    a float32 inner-product corpus it is the bfloat16 matmul. Either way
    it fails ``dist_gap`` and only that."""
    cfg = tiny_config(dtype, metric)
    v = control.control_verdict(cfg, seed, 256)
    checks = v["checks"]
    assert not v["correct"]
    if dtype == "float32":
        assert checks["dist_gap"]["value"] > 3 * checks["dist_gap"]["limit"]
    else:
        # over ten integer units at the widest scale 2 * dim * 255^2, so
        # more than a distance off by one unit, which the limit 0 catches
        assert checks["dist_gap"]["value"] > 10 / (2 * cfg["dim"] * 255**2)
    assert checks["lost"]["value"] == 0 and checks["bad_rows"]["value"] == 0
    assert checks["recall"]["value"] >= checks["recall"]["limit"]
