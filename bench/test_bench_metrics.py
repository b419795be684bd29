"""Metric arithmetic, the comparison that decides ``correct``, and the
peaks table, on hand-made inputs."""

import math

import numpy as np
import pytest

from bench import check, metrics_lib, run


def ctx(**kw):
    n = len(kw.get("latency_s", [0.0]))
    base = dict(config={}, peaks={}, setup_s=1.0, window_s=1.0,
                answered=np.ones(n, bool), latency_s=np.zeros(n),
                good=np.ones(n, bool), recall=1.0, flush_sizes=[],
                buckets=(64,))
    base.update(kw)
    return run.Context(**base)


def reader(name):
    return run.load_reader(name)


def test_p99_counts_a_shed_query_as_a_miss():
    lat = np.linspace(0.001, 0.1, 100)
    answered = np.ones(100, bool)
    assert reader("p99_ms")(ctx(latency_s=lat, answered=answered)) \
        == pytest.approx(99.0)
    # the fastest query shed: it is now the slowest, and p99 (rank 99 of
    # 100) lands on the former maximum
    answered[0] = False
    assert reader("p99_ms")(ctx(latency_s=lat, answered=answered)) \
        == pytest.approx(100.0)
    answered[1] = False
    assert reader("p99_ms")(ctx(latency_s=lat, answered=answered)) \
        == math.inf
    # the median moves too: two misses push it up by two ranks
    assert reader("p50_ms")(ctx(latency_s=lat, answered=answered)) \
        == pytest.approx(52.0)


def test_unanswered_query_latency_nan_counts_as_a_miss():
    lat = np.array([0.01, np.nan, 0.02, 0.03])
    answered = ~np.isnan(lat)
    assert metrics_lib.percentile_ms(lat, answered, 99) == math.inf
    assert metrics_lib.percentile_ms(lat, answered, 50) == pytest.approx(20.0)


def test_qps_is_all_the_good_work_over_all_the_window():
    good = np.array([True] * 90 + [False] * 10)
    c = ctx(latency_s=np.zeros(100), good=good, window_s=2.5)
    assert reader("qps")(c) == pytest.approx(36.0)


def test_setup_and_recall_readers():
    c = ctx(setup_s=12.5, recall=0.93)
    assert reader("setup_s")(c) == 12.5
    assert reader("recall_at_10")(c) == 0.93


def test_flush_fill_on_hand_made_flushes():
    ladder = (1, 2, 4, 8, 16, 32, 64)
    # 64 -> 64, 3 -> 4, 5 -> 8, 1 -> 1: 73 rows in 77 padded rows
    assert metrics_lib.flush_fill([64, 3, 5, 1], ladder) \
        == pytest.approx(100 * 73 / 77)
    assert reader("flush_fill.poisson")(
        ctx(flush_sizes=[33, 64], buckets=ladder)) \
        == pytest.approx(100 * 97 / 128)
    assert metrics_lib.flush_fill([], ladder) is None
    with pytest.raises(ValueError):
        metrics_lib.bucket_for(65, ladder)


def test_lockstep_waste_on_hand_made_hops():
    # one flush, 1 shard x 4 lanes: hops 4, 2, 2, 0 -> 4 * 4 / 8
    hops = np.array([[[4, 2, 2, 0]]])
    assert metrics_lib.lockstep_waste(hops) == pytest.approx(2.0)
    # two flushes: (4 * 4 + 4 * 1) / (8 + 4)
    hops = np.array([[[4, 2, 2, 0]], [[1, 1, 1, 1]]])
    assert metrics_lib.lockstep_waste(hops) == pytest.approx(20 / 12)
    assert reader("lockstep_waste.batch")(ctx(hops=hops)) \
        == pytest.approx(20 / 12)
    assert reader("lockstep_waste.batch")(ctx()) is None
    assert metrics_lib.lockstep_waste(np.zeros((1, 1, 4))) is None


def test_readers_without_a_trace_return_nothing():
    for name in ("idle_share.batch", "step_ms.batch", "idle_share.poisson"):
        assert reader(name)(ctx()) is None


def test_unknown_device_kind_raises():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        run.peaks_for("TPU v9")


def test_unknown_metric_has_no_reader():
    with pytest.raises(FileNotFoundError):
        run.load_reader("no_such_metric.batch")


LIMITS = {"dist_gap": 1e-5, "recall": 0.5}


def verdict(ids, dists, answered, shed, ref, exact_d=None):
    exact_d = dists if exact_d is None else exact_d
    return check.judge(ids=ids, dists=dists, answered=answered, shed=shed,
                       ref_ids=ref, exact_d=exact_d,
                       scale=np.full(ids.shape, 100.0), n_corpus=100,
                       limits=LIMITS)


def sound():
    ids = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9]])
    dists = np.array([[1.0, 2.0, 3.0]] * 3)
    return ids, dists, np.ones(3, bool), np.zeros(3, bool), ids.copy()


def test_judge_passes_a_sound_run():
    v = verdict(*sound())
    assert v["correct"] and v["recall"] == 1.0
    assert list(v["checks"]) == ["lost", "bad_rows", "dist_gap", "recall"]


def test_judge_counts_shed_as_failed_not_incorrect():
    ids, dists, answered, shed, ref = sound()
    answered[1], shed[1] = False, True
    v = verdict(ids, dists, answered, shed, ref)
    assert v["correct"] and v["checks"]["lost"]["value"] == 0


def test_judge_fails_a_lost_answer():
    ids, dists, answered, shed, ref = sound()
    answered[2] = False
    v = verdict(ids, dists, answered, shed, ref)
    assert not v["correct"] and v["checks"]["lost"]["value"] == 1


@pytest.mark.parametrize("fault", ["range", "repeat", "order", "inf"])
def test_judge_fails_a_malformed_row(fault):
    ids, dists, answered, shed, ref = sound()
    if fault == "range":
        ids[0, 2] = 100
    elif fault == "repeat":
        ids[0, 2] = 1
    elif fault == "order":
        dists[0] = [3.0, 2.0, 1.0]
    else:
        dists[0, 2] = np.inf
    v = verdict(ids, dists, answered, shed, ref)
    assert not v["correct"] and v["checks"]["bad_rows"]["value"] == 1


def test_judge_fails_a_distance_that_is_not_its_ids():
    ids, dists, answered, shed, ref = sound()
    exact = dists.copy()
    exact[1, 1] += 0.01            # a gap of 1e-4 of the scale
    v = verdict(ids, dists, answered, shed, ref, exact)
    assert not v["correct"]
    assert v["checks"]["dist_gap"]["value"] == pytest.approx(1e-4)


def test_judge_fails_low_recall():
    ids, dists, answered, shed, ref = sound()
    ref = ref + 50
    v = verdict(ids, dists, answered, shed, ref)
    assert not v["correct"] and v["checks"]["recall"]["value"] == 0.0


def test_steady_rate_leaves_out_the_first_completions():
    # 4 flushes of 64 due at 0, done at 1, 2, 3, 4 s: 192 queries in 3 s
    lat = np.repeat([1.0, 2.0, 3.0, 4.0], 64)
    assert run.steady_rate(lat, np.zeros(256)) == pytest.approx(64.0)
