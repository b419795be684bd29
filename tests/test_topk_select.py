"""Pallas streaming k-selection kernels (kernels/topk_select.py): bitwise
kernel-vs-ref parity across shapes incl. pads, duplicates and ties; the
rerank bit-parity regression vs the pre-kernel double-argsort path; and the
ops.py dispatch seam (REPRO_FORCE_PALLAS / REPRO_KERNEL_MIN_ROWS)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, topk_select as tk
from repro.kernels import ref as R


def _cand_set(rng, q, c, with_ties=True):
    """Duplicate-heavy candidates: dups, -1 pads, an all-pad row, a
    single-id row, and exact distance ties across distinct columns."""
    ids = rng.integers(-1, max(2, c // 2), (q, c)).astype(np.int32)
    d = rng.random((q, c)).astype(np.float32)
    ids[:, -2:] = -1                       # trailing pads everywhere
    if q > 1:
        ids[0, :] = -1                     # all-pad row
    if q > 2:
        ids[1, :] = 7                      # one id repeated across the row
    if with_ties and c >= 8:
        d[:, 3:7] = 0.5                    # 4-way exact tie, distinct cols
    return jnp.asarray(ids), jnp.asarray(d)


@pytest.mark.parametrize("q,c,k", [
    (1, 8, 4), (3, 33, 5), (4, 64, 10), (7, 300, 10), (8, 512, 16),
    (2, 10, 10),   # k == c
])
def test_topk_select_kernel_bitwise_matches_ref(rng, q, c, k):
    ids, d = _cand_set(rng, q, c)
    ri, rd = R.topk_select_ref(ids, d, k=k)
    ki, kd = tk.topk_select(ids, d, k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))


@pytest.mark.parametrize("q,o,run,k", [
    (1, 1, 4, 4), (4, 3, 10, 10), (7, 4, 5, 5), (5, 8, 10, 10),
    (3, 5, 10, 10),   # non-pow2 run count
    (2, 6, 12, 7),    # run != k
])
def test_merge_topk_kernel_bitwise_matches_ref(rng, q, o, run, k):
    # pre-sorted disjoint runs with unfilled tails and cross-run ties —
    # the sharded sink's slot layout
    d3 = np.sort(rng.random((q, o, run)).astype(np.float32), axis=-1)
    ids3 = np.arange(q * o * run, dtype=np.int32).reshape(q, o, run)
    d3[:, 0, -2:] = np.inf
    ids3[:, 0, -2:] = -1
    if o > 1:
        d3[:, 1, 0] = d3[:, 0, 0]          # exact tie across runs
        d3[:, 1] = np.sort(d3[:, 1], axis=-1)
    if q > 1:
        d3[1] = np.inf                     # fully-unanswered query
        ids3[1] = -1
    ids = jnp.asarray(ids3.reshape(q, o * run))
    d = jnp.asarray(d3.reshape(q, o * run))
    ri, rd = R.merge_topk_ref(ids, d, k=k)
    ki, kd = tk.merge_topk(ids, d, k=k, interpret=True)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))


def test_rerank_bit_parity_vs_double_argsort_reference(rng):
    """Satellite pin: the scatter-built inverse permutation (and the kernel
    seam) must reproduce the previous double-stable-argsort rerank
    BIT-FOR-BIT, including duplicates, pads, all-pad rows and top_k ties."""
    from repro.core.rerank import rerank
    Q, C, N, D, k = 7, 48, 120, 8, 6
    ids = rng.integers(-1, 30, (Q, C)).astype(np.int32)
    ids[0, :] = -1
    ids[1, :] = 11
    q = rng.normal(size=(Q, D)).astype(np.float32)
    v = rng.normal(size=(N, D)).astype(np.float32)
    v[3] = v[4]          # distinct ids, identical vectors -> tied distances

    q2 = jnp.sum(jnp.asarray(q) ** 2, axis=-1, keepdims=True)
    cand = jnp.asarray(v)[jnp.clip(jnp.asarray(ids), 0)]
    c2 = jnp.sum(cand * cand, axis=-1)
    dots = jnp.einsum("qd,qcd->qc", jnp.asarray(q), cand)
    d2 = q2 + c2 - 2.0 * dots
    # the PREVIOUS implementation: stable argsort dedup + argsort-of-argsort
    # inverse permutation + lax.top_k
    order = jnp.argsort(jnp.asarray(ids), axis=-1, stable=True)
    sorted_ids = jnp.take_along_axis(jnp.asarray(ids), order, axis=-1)
    dup_sorted = jnp.concatenate(
        [jnp.zeros_like(sorted_ids[:, :1], bool),
         sorted_ids[:, 1:] == sorted_ids[:, :-1]], axis=-1)
    inv = jnp.argsort(order, axis=-1, stable=True)
    dup = jnp.take_along_axis(dup_sorted, inv, axis=-1)
    d2m = jnp.where((jnp.asarray(ids) < 0) | dup, jnp.inf, d2)
    neg, pos = jax.lax.top_k(-d2m, k)
    old_ids = jnp.take_along_axis(jnp.asarray(ids), pos, axis=-1)
    old_d = -neg
    old_ids = jnp.where(jnp.isfinite(old_d), old_ids, -1)

    out = rerank(jnp.asarray(q), jnp.asarray(ids), jnp.asarray(v), k=k)
    np.testing.assert_array_equal(np.asarray(out.ids), np.asarray(old_ids))
    np.testing.assert_array_equal(np.asarray(out.dists), np.asarray(old_d))


def test_topk_refs_shared_by_kernel_and_xla_paths(rng, monkeypatch):
    """ops dispatch: forced-Pallas output == default (ref) output bitwise
    for both selection ops, mirroring test_ops_dispatch_paths."""
    ids, d = _cand_set(rng, 5, 40)
    runs_d = jnp.asarray(np.sort(
        rng.random((5, 4, 5)).astype(np.float32), -1).reshape(5, 20))
    runs_i = jnp.asarray(np.arange(100, dtype=np.int32).reshape(5, 20))
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    monkeypatch.setenv("REPRO_KERNEL_MIN_ROWS", "8")
    ki, kd = ops.topk_select(ids, d, k=5)
    mi, md = ops.merge_topk(runs_i, runs_d, k=5)
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    monkeypatch.delenv("REPRO_KERNEL_MIN_ROWS")
    ri, rd = ops.topk_select(ids, d, k=5)
    ni, nd = ops.merge_topk(runs_i, runs_d, k=5)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kd), np.asarray(rd))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(ni))
    np.testing.assert_array_equal(np.asarray(md), np.asarray(nd))


def test_kernel_min_rows_env_override(monkeypatch):
    """REPRO_KERNEL_MIN_ROWS lowers/raises the dispatch threshold; bad
    values are rejected loudly (not silently ignored)."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    assert not ops.prefer_kernel(8)           # default threshold is 256
    assert ops.prefer_kernel(256)
    monkeypatch.setenv("REPRO_KERNEL_MIN_ROWS", "8")
    assert ops.prefer_kernel(8)
    assert not ops.prefer_kernel(7)
    monkeypatch.setenv("REPRO_KERNEL_MIN_ROWS", "0")
    assert ops.prefer_kernel(1)
    monkeypatch.setenv("REPRO_KERNEL_MIN_ROWS", "not-a-number")
    with pytest.raises(ValueError, match="REPRO_KERNEL_MIN_ROWS"):
        ops.prefer_kernel(512)
    monkeypatch.setenv("REPRO_KERNEL_MIN_ROWS", "-1")
    with pytest.raises(ValueError, match=">= 0"):
        ops.prefer_kernel(512)
    monkeypatch.delenv("REPRO_FORCE_PALLAS")
    monkeypatch.delenv("REPRO_KERNEL_MIN_ROWS")
    assert ops.prefer_kernel(512) == (jax.default_backend() == "tpu")
