"""The chip smoke run's body at a tiny size on the CPU: its control flow,
its checks and its refusal to run without a TPU."""

import json
import pathlib
import types

import jax
import numpy as np
import pytest

from repro.launch import chip_smoke as cs
from repro.launch import compile_cache

TINY = cs.SmokeConfig(n=3000, n_clusters=12, kmeans_sample=0, n_queries=64,
                      recall_floor=0.5)


def test_smoke_one_chip_tiny_passes():
    lines = []
    out = cs.smoke_one_chip(TINY, lines.append)
    assert out["recall"] >= TINY.recall_floor
    assert out["tpu_custom_call"] is False          # interpret mode on CPU
    text = "\n".join(lines)
    for phase in ("kernel topk_select C=320: matches", "kernel cluster_scan",
                  "corpus: 3,000 x 128", "compiles in served window 0",
                  "shed 0", "rows with -1 ids 0", "not a benchmark"):
        assert phase in text, phase


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs >= 4 devices (REPRO_FORCE_HOST_DEVICES=4)")
def test_smoke_mesh_tiny_matches_inproc():
    out = cs.smoke_mesh(TINY, 4, lambda _: None)
    assert out == {"devices": 4, "same_ids": True}


def _report(**kw):
    ids = np.tile(np.arange(10, dtype=np.int32), (4, 1))
    base = dict(ids=ids, shed=np.zeros(4, bool), n_shed=0, n_queries=4,
                n_flushes=1, p50_ms=1.0, p99_ms=2.0)
    base.update(kw)
    return types.SimpleNamespace(**base)


TRUTH = np.tile(np.arange(10), (4, 1))


@pytest.mark.parametrize("case,kw,in_window,match", [
    ("shed", dict(n_shed=1, shed=np.array([1, 0, 0, 0], bool)), 0, "shed"),
    ("neg", dict(ids=np.where(np.arange(10) == 3, -1,
                              np.tile(np.arange(10), (4, 1))).astype(
                                  np.int32)), 0, "-1 ids"),
    ("compile", {}, 1, "compiles inside the served window"),
    ("recall", dict(ids=np.tile(np.arange(10, 20, dtype=np.int32), (4, 1))),
     0, "recall"),
])
def test_check_served_fails(case, kw, in_window, match):
    with pytest.raises(cs.SmokeFailed, match=match):
        cs._check_served(_report(**kw), TRUTH, TINY, in_window, case,
                         lambda _: None)


def test_check_served_passes_clean_report():
    assert cs._check_served(_report(), TRUTH, TINY, 0, "ok",
                            lambda _: None) == 1.0


def test_main_refuses_without_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert cs.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""
    assert "needs a TPU" in out.err
    with pytest.raises(json.JSONDecodeError):
        json.loads(out.out or "{")


@pytest.mark.parametrize("env", [None, "/somewhere/cache"])
def test_compile_cache_dir(monkeypatch, env):
    prev = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    try:
        got = compile_cache.enable_compile_cache()
        if env is None:
            root = pathlib.Path(__file__).resolve().parents[1]
            assert got == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
        else:
            assert got == env
            assert jax.config.jax_compilation_cache_dir == prev
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
