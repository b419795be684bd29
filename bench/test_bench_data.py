"""The generator, the traffic mixes and the brute-force reference."""

import dataclasses
import hashlib
import json

import jax
import numpy as np
import pytest

from bench import arrivals, check, corpus
from bench.conftest import BENCH, QUANTIZE, tiny_config

MIX = corpus.Mixture(components=4, rank=3, center_scale=0.5, noise=0.1)


def mix_in(dtype):
    """MIX drawn in ``dtype``, quantized as the tiny configurations are."""
    if dtype == "float32":
        return MIX
    q = QUANTIZE[dtype]
    return dataclasses.replace(MIX, dtype=dtype,
                               quantize=(q["scale"], q["offset"]))


def draw(seed, n=512, nq=64, dim=12, mix=MIX):
    key = corpus.seed_key(seed)
    return (np.asarray(corpus.make_corpus(key, n=n, dim=dim, mix=mix)),
            np.asarray(corpus.make_queries(key, n=nq, dim=dim, mix=mix)))


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


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
@pytest.mark.parametrize("dtype", ["uint8", "int8"])
def test_integer_draws_in_their_dtype_and_range(dtype, seed):
    mix = mix_in(dtype)
    x1, q1 = draw(seed, mix=mix)
    x2, q2 = draw(seed, mix=mix)
    assert x1.dtype == np.dtype(dtype) and q1.dtype == np.dtype(dtype)
    np.testing.assert_array_equal(x1, x2)
    np.testing.assert_array_equal(q1, q2)
    x3, q3 = draw(seed + 1, mix=mix)
    assert not np.array_equal(x1, x3) and not np.array_equal(q1, q3)
    lo, hi = corpus.RANGES[dtype]
    both = np.concatenate([x1, q1])
    assert both.min() >= lo and both.max() <= hi
    # the quantized values are the float32 draw, rounded: the same
    # mixture, and at this quantize almost nothing lies on the clip
    xf, _ = draw(seed)
    scale, offset = mix.quantize
    want = np.clip(np.round(offset + scale * xf.astype(np.float64)), lo, hi)
    assert np.abs(x1 - want).max() <= 1
    assert np.mean((both == lo) | (both == hi)) < 0.01


def test_query_shift_moves_only_the_queries():
    x0, q0 = draw(4)
    xz, qz = draw(4, mix=dataclasses.replace(MIX, query_shift=0.0))
    np.testing.assert_array_equal(q0, qz)
    xs, qs = draw(4, mix=dataclasses.replace(MIX, query_shift=1.0))
    np.testing.assert_array_equal(x0, xs)
    assert not np.array_equal(q0, qs)

    def nn(q, x):
        return np.sort(((q[:, None, :] - x[None]) ** 2).sum(-1), axis=1)[:, 0]
    # out of distribution: the queries lie farther from the corpus
    assert np.median(nn(qs, x0)) > 2 * np.median(nn(q0, x0))


@pytest.mark.parametrize("change,match", [
    ({"dtype": "float16"}, "dtype"),
    ({"metric": "cosine"}, "metric"),
    ({"generator.quantize": {"scale": 1.0, "offset": 0.0}}, "quantize"),
    ({"dtype": "uint8"}, "quantize"),
    ({"dtype": "int8", "generator.quantize": {"scale": 1.0}}, "quantize"),
    ({"generator.query_shift": -1.0}, "query_shift"),
    ({"dtype": "uint8", "dim": 259, "generator.quantize": QUANTIZE["uint8"]},
     "exact"),
    ({"dtype": "int8", "dim": 259, "generator.quantize": QUANTIZE["int8"]},
     "exact"),
    ({"dtype": "int8", "metric": "ip", "dim": 1025,
      "generator.quantize": QUANTIZE["int8"]}, "exact"),
])
def test_a_configuration_outside_the_harness_is_refused(change, match):
    cfg = tiny_config()
    for key, value in change.items():
        if key.startswith("generator."):
            cfg["generator"][key.split(".")[1]] = value
        else:
            cfg[key] = value
    with pytest.raises(ValueError, match=match):
        corpus.Mixture.from_config(cfg)


@pytest.mark.parametrize("dtype,metric,dim", [
    ("uint8", "l2", 258), ("uint8", "ip", 258), ("int8", "l2", 258),
    ("int8", "ip", 1024)])
def test_the_exact_range_ends_where_the_docstring_says(dtype, metric, dim):
    """At the widest dimension taken, the reference is exact on the
    vectors farthest apart (for ``l2``) or longest (for ``ip``); one more
    dimension is refused."""
    cfg = tiny_config(dtype, metric)
    cfg["dim"] = dim
    assert corpus.Mixture.from_config(cfg).dtype == dtype
    lo, hi = corpus.RANGES[dtype]
    x = np.array([[lo] * dim, [hi] * dim, [lo, hi] * (dim // 2),
                  [hi - 1] * dim], dtype)
    q = x[[0, 1, 2]]
    ids, d = corpus.exact_knn(q, jax.numpy.asarray(x), 4, metric=metric)
    want_ids, want_d = numpy_knn(q, x, 4, metric)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(d, want_d)
    ed, _ = corpus.exact_dists(q, ids, jax.numpy.asarray(x), metric=metric)
    np.testing.assert_array_equal(ed, want_d)
    with pytest.raises(ValueError, match="exact"):
        corpus.exact_knn(np.zeros((1, dim + 1)),
                         jax.numpy.zeros((4, dim + 1), dtype), 1,
                         metric=metric)


@pytest.mark.parametrize("dtype", ["uint8", "int8", "float32"])
def test_integer_corpus_holds_no_float32_copy(dtype):
    """The compiled generator of an integer corpus holds no (n, dim)
    float32 buffer: its output is n * dim * itemsize bytes, and output and
    temporaries (a component at a time, 1.7 MB here) together are less
    than the float32 copy's 16.8 MB."""
    n, dim = 65536, 64
    mix = dataclasses.replace(mix_in(dtype), components=64)
    compiled = corpus.make_corpus.lower(corpus.seed_key(0), n=n, dim=dim,
                                        mix=mix).compile()
    mem = compiled.memory_analysis()
    if mem is None:
        pytest.skip("the backend reports no memory analysis")
    width = np.dtype(dtype).itemsize
    assert mem.output_size_in_bytes == n * dim * width
    if dtype != "float32":
        assert mem.temp_size_in_bytes + mem.output_size_in_bytes \
            < n * dim * 4


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


# sha256 (first 16 hex digits) of the float32 draw, reference and exact
# distances below, computed before the harness took other dtypes and
# metrics: the float32 L2 path is pinned bit for bit
PIN = {"corpus": "771d855922a03567", "pool": "8ca846da21daf60e",
       "ref_ids": "93df3e799fc40b89", "ref_dists": "fd5c9ca3ddc81244",
       "exact_d": "df4754350b4c4045", "scale": "74010744ec44e15a"}


@pytest.fixture(scope="module")
def float32_pinned():
    key = corpus.seed_key(2**31 + 77)
    x = corpus.make_corpus(key, n=512, dim=12, mix=MIX)
    q = np.asarray(corpus.make_queries(key, n=64, dim=12, mix=MIX))
    ids, d = corpus.exact_knn(q, x, 10, q_block=24, x_block=100)
    ed, sc = corpus.exact_dists(q, ids, x, block=24)
    return {"corpus": x, "pool": q, "ref_ids": ids, "ref_dists": d,
            "exact_d": ed, "scale": sc}


@pytest.mark.parametrize("name", sorted(PIN))
def test_float32_draw_and_reference_are_pinned(float32_pinned, name):
    a = np.ascontiguousarray(np.asarray(float32_pinned[name]))
    assert hashlib.sha256(a.tobytes()).hexdigest()[:16] == PIN[name]


def numpy_knn(q, x, k, metric="l2"):
    """Brute force in int64 for an integer corpus, else float64."""
    wide = np.int64 if x.dtype.kind in "iu" else np.float64
    q, x = q.astype(wide), x.astype(wide)
    if metric == "ip":
        d = -(q @ x.T)
    else:
        d = ((q[:, None, :] - x[None]) ** 2).sum(-1)
    ids = np.argsort(d, axis=1, kind="stable")[:, :k]
    return ids, np.take_along_axis(d, ids, 1)


@pytest.mark.parametrize("q_block,x_block,dtype,metric", [
    pytest.param(64, 512, "float32", "l2", id="64-512"),
    pytest.param(24, 100, "float32", "l2", id="24-100"),
    pytest.param(7, 37, "float32", "l2", id="7-37"),
] + [pytest.param(qb, xb, dt, m, id=f"{dt}-{m}-{qb}-{xb}")
     for dt, m in [("uint8", "l2"), ("int8", "l2"), ("float32", "ip")]
     for qb, xb in [(64, 512), (7, 37)]])
def test_device_reference_equals_numpy_brute_force(q_block, x_block, dtype,
                                                   metric):
    x, q = draw(11, mix=mix_in(dtype))
    ids, d = corpus.exact_knn(q, jax.numpy.asarray(x), 10, metric=metric,
                              q_block=q_block, x_block=x_block)
    want_ids, want_d = numpy_knn(q, x, 10, metric)
    np.testing.assert_array_equal(ids, want_ids)
    if dtype == "float32":
        np.testing.assert_allclose(d, want_d, rtol=1e-5, atol=1e-5)
    else:
        # integer distances are exact
        np.testing.assert_array_equal(d, want_d)


@pytest.mark.parametrize("dtype,metric", [("uint8", "l2"), ("int8", "l2"),
                                          ("float32", "ip"), ("int8", "ip")])
def test_exact_dists_per_metric(dtype, metric):
    x, q = draw(12, mix=mix_in(dtype))
    ids = np.random.default_rng(1).integers(0, len(x), (len(q), 10))
    d, scale = corpus.exact_dists(q, ids, jax.numpy.asarray(x),
                                  metric=metric, block=24)
    qw, xw = q.astype(np.float64), x[ids].astype(np.float64)
    if metric == "ip":
        want = -(qw[:, None, :] * xw).sum(-1)
        want_scale = np.linalg.norm(qw, axis=1)[:, None] \
            * np.linalg.norm(xw, axis=-1)
    else:
        want = ((qw[:, None, :] - xw) ** 2).sum(-1)
        want_scale = (qw ** 2).sum(1)[:, None] + (xw ** 2).sum(-1)
    if dtype == "float32":
        np.testing.assert_allclose(d, want, rtol=1e-5, atol=1e-5)
    else:
        np.testing.assert_array_equal(d, want)
    np.testing.assert_allclose(scale, want_scale, rtol=1e-6)


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
