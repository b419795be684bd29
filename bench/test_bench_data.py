"""The generator, the traffic mixes and the brute-force reference."""

import json

import jax
import numpy as np
import pytest

from bench import arrivals, check, corpus
from bench.conftest import BENCH

MIX = corpus.Mixture(components=4, rank=3, center_scale=0.5, noise=0.1)


def draw(seed, n=512, nq=64, dim=12):
    key = corpus.seed_key(seed)
    return (np.asarray(corpus.make_corpus(key, n=n, dim=dim, mix=MIX)),
            np.asarray(corpus.make_queries(key, n=nq, dim=dim, mix=MIX)))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**33 + 1])
def test_generator_deterministic_with_configured_shapes(seed):
    x1, q1 = draw(seed)
    x2, q2 = draw(seed)
    assert x1.shape == (512, 12) and q1.shape == (64, 12)
    assert x1.dtype == np.float32 and q1.dtype == np.float32
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(q1, q2)
    x3, q3 = draw(seed + 1)
    assert not np.array_equal(x1, x3) and not np.array_equal(q1, q3)


def test_queries_are_held_out_from_the_same_mixture():
    x, q = draw(3)
    # no query is a corpus row, but each lies near its component
    d = ((q[:, None, :] - x[None]) ** 2).sum(-1)
    assert d.min() > 0
    nn = np.sort(d, axis=1)[:, 0]
    spread = ((x - x.mean(0)) ** 2).sum(1).mean()
    assert np.median(nn) < 0.5 * spread


def test_corpus_rejects_a_size_the_components_do_not_divide():
    with pytest.raises(ValueError, match="multiple"):
        corpus.make_corpus(corpus.seed_key(0), n=510, dim=12, mix=MIX)


def test_seed_must_be_non_negative():
    with pytest.raises(ValueError):
        corpus.seed_key(-1)


def test_wide_seeds_give_their_own_key():
    k = corpus.seed_key(2**33 + 1)
    assert k.shape == (2,) and k.dtype == np.uint32
    assert not np.array_equal(k, corpus.seed_key(1))


def numpy_knn(q, x, k):
    d = ((q[:, None, :].astype(np.float64) - x[None]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.mark.parametrize("q_block,x_block", [(64, 512), (24, 100), (7, 37)])
def test_device_reference_equals_numpy_brute_force(q_block, x_block):
    x, q = draw(11)
    ids, d = corpus.exact_knn(q, jax.numpy.asarray(x), 10, q_block=q_block,
                              x_block=x_block)
    want_ids, want_d = numpy_knn(q, x, 10)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(d, want_d, rtol=1e-5, atol=1e-5)


def test_exact_dists_are_the_difference_form():
    x, q = draw(12)
    ids = np.random.default_rng(0).integers(0, len(x), (len(q), 10))
    d, scale = corpus.exact_dists(q, ids, jax.numpy.asarray(x), block=24)
    want = ((q[:, None, :].astype(np.float64) - x[ids]) ** 2).sum(-1)
    np.testing.assert_allclose(d, want, rtol=1e-6)
    np.testing.assert_allclose(
        scale, (q ** 2).sum(1)[:, None] + (x[ids] ** 2).sum(-1), rtol=1e-6)


def test_control_dot_is_bfloat16_and_the_reference_is_not():
    x, q = draw(13, dim=64)
    xd = jax.numpy.asarray(x)
    want = q.astype(np.float64) @ x.T.astype(np.float64)
    hi = np.asarray(corpus.highest_dot(jax.numpy.asarray(q), xd))
    lo = np.asarray(check.bf16_dot(jax.numpy.asarray(q), xd))
    # errors over |q| |x|: float32 rounding against bfloat16 rounding
    scale = np.linalg.norm(q, axis=1)[:, None] * np.linalg.norm(x, axis=1)
    assert (np.abs(hi - want) / scale).max() < 1e-6
    assert (np.abs(lo - want) / scale).max() > 1e-4


def mix_file(name):
    return arrivals.load_mix(BENCH / "traffic" / f"{name}.json")


def test_at_once_stream_is_whole_flushes_sized_from_the_rate():
    mix = mix_file("batch")
    order, arr = arrivals.stream(mix, 1000, seed=5, seconds=2.0,
                                 rate_hint=300.0)
    assert len(order) == 640 and len(order) % mix["multiple"] == 0
    assert (arr == 0).all()
    assert set(order) <= set(range(1000))
    # one pass over the pool before any query repeats
    assert len(set(order[:640])) == 640
    with pytest.raises(ValueError):
        arrivals.stream(mix, 1000, seed=5, seconds=2.0)


@pytest.mark.parametrize("seed", [1, 2**32 + 9])
def test_poisson_stream_has_a_fixed_count_and_sorted_arrivals(seed):
    mix = mix_file("poisson")
    order, arr = arrivals.stream(mix, 10_000, seed, seconds=3.0)
    assert len(order) == round(mix["rate_qps"] * 3.0) == len(arr)
    assert (np.diff(arr) >= 0).all() and arr[0] >= 0 and arr[-1] < 3.0
    o2, a2 = arrivals.stream(mix, 10_000, seed, seconds=3.0)
    np.testing.assert_array_equal(order, o2)
    np.testing.assert_array_equal(arr, a2)
    o3, a3 = arrivals.stream(mix, 10_000, seed + 1, seconds=3.0)
    assert len(o3) == len(order) and not np.array_equal(a3, arr)


def test_mix_files_load(tmp_path):
    for path in (BENCH / "traffic").glob("*.json"):
        arrivals.load_mix(path)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"arrivals": "poisson"}))
    with pytest.raises(ValueError):
        arrivals.load_mix(bad)
