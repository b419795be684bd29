"""The control comes out as not correct: the reference in the program's
place with its matmul in bfloat16, one step below the configuration's
float32, judged as a run judges the program (small size; on the chip it
runs at the cells' sizes, PERF.md)."""

import pytest

from bench import control


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
