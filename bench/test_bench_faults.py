"""A whole run of the harness at a small size on the CPU (past its look for
a chip), once sound and once with each fault a serving cell can have
planted in the timed path underneath: ``correct`` has to come out false
for every fault."""

import numpy as np
import pytest

from bench import arrivals, run
from bench.conftest import BENCH, tiny_config
from repro.core import engine, pipeline, rerank


# the latency metrics of an open-loop cell (sift1m.poisson is not in
# BENCHMARK.json yet: PERF.md, Open questions)
LATENCY = [{"name": n, "unit": "ms", "better": "lower", "bound": 0.25,
            "source": "host_clock", "workloads": ["sift1m.poisson"]}
           for n in ("p50_ms", "p99_ms")]


def run_tiny(cfg, bench_json, traffic="batch", seed=5):
    mix = arrivals.load_mix(BENCH / "traffic" / f"{traffic}.json")
    if traffic == "poisson":
        mix["rate_qps"] = 100.0
        bench_json = dict(bench_json,
                          end_to_end=bench_json["end_to_end"] + LATENCY)
    cell = {"name": f"sift1m.{traffic}", "chips": 1}
    return run.run_cell(bench_json, cell, cfg, mix, seed, 0.5, False,
                        log=lambda m: None)


@pytest.mark.parametrize("traffic", ["batch", "poisson"])
def test_sound_run_is_correct(tiny_cfg, bench_json, traffic):
    out = run_tiny(tiny_cfg, bench_json, traffic)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    names = set(out["metrics"])
    assert "setup_s" in names and "recall_at_10" in names
    assert ("qps" in names) == (traffic == "batch")
    assert ("p99_ms" in names) == (traffic == "poisson")


def test_half_of_each_flush_left_out(tiny_cfg, bench_json, monkeypatch):
    finish = pipeline.EngineWorker._finish

    def half(self, idxs, res, t):
        # the first half of the flush's rows is answered; the rest never is
        n = (len(idxs) + 1) // 2
        res = rerank.RerankResult(res.ids[:n], res.dists[:n])
        return finish(self, idxs[:n], res, t)

    monkeypatch.setattr(pipeline.EngineWorker, "_finish", half)
    out = run_tiny(tiny_cfg, bench_json)
    assert not out["correct"]
    assert out["checks"]["lost"]["value"] > 0


def test_answer_altered_where_produced(tiny_cfg, bench_json, monkeypatch):
    search = engine.PIMCQGEngine.search

    def altered(self, queries, **kw):
        out, stats = search(self, queries, **kw)
        n = self.host.vectors.shape[0]
        ids = out.ids.at[:, -1].set((out.ids[:, -1] + 1) % n)
        return rerank.RerankResult(ids, out.dists), stats

    monkeypatch.setattr(engine.PIMCQGEngine, "search", altered)
    out = run_tiny(tiny_cfg, bench_json)
    assert not out["correct"]
    c = out["checks"]
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"] \
        or c["bad_rows"]["value"] > 0


def test_step_returns_its_previous_state(tiny_cfg, bench_json, monkeypatch):
    search = engine.PIMCQGEngine.search
    last = {}

    def stale(self, queries, **kw):
        out = search(self, queries, **kw)
        prev = last.get("out", out)
        last["out"] = out
        return prev

    monkeypatch.setattr(engine.PIMCQGEngine, "search", stale)
    out = run_tiny(tiny_cfg, bench_json)
    assert not out["correct"]
    assert out["checks"]["dist_gap"]["value"] \
        > out["checks"]["dist_gap"]["limit"]


def test_shed_queries_fail_but_are_not_incorrect(tiny_cfg, bench_json,
                                                 monkeypatch):
    """Overload sheds: the shed queries count in ``failed``, and the run
    stays correct (a refusal says nothing wrong)."""
    tiny_cfg["topology"] = {"admission_depth": 1, "shed_deadline_s": 1e-4}
    out = run_tiny(tiny_cfg, bench_json, "poisson")
    assert out["failed"] > 0
    assert out["correct"], out["checks"]
    assert np.isinf(out["metrics"]["p99_ms"]["value"])


def test_integer_run_is_correct(bench_json):
    """A uint8 L2 configuration served by the program as it is (which
    casts corpus and queries to float32): exact distances, correct."""
    out = run_tiny(tiny_config("uint8"), bench_json)
    assert out["correct"], out["checks"]
    assert out["checks"]["dist_gap"]["value"] == 0.0


@pytest.mark.parametrize("fault", ["wrong_id", "distance_plus_one"])
def test_integer_run_with_a_fault_is_not_correct(bench_json, monkeypatch,
                                                 fault):
    """On a uint8 corpus an id altered where it is produced, or a distance
    off by one integer unit, makes the run not correct."""
    search = engine.PIMCQGEngine.search

    def altered(self, queries, **kw):
        out, stats = search(self, queries, **kw)
        ids, dists = out.ids, out.dists
        if fault == "wrong_id":
            n = self.host.vectors.shape[0]
            ids = ids.at[:, -1].set((ids[:, -1] + 1) % n)
        else:
            dists = dists.at[:, -1].add(1.0)
        return rerank.RerankResult(ids, dists), stats

    monkeypatch.setattr(engine.PIMCQGEngine, "search", altered)
    out = run_tiny(tiny_config("uint8"), bench_json)
    assert not out["correct"]
    c = out["checks"]
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]


@pytest.mark.parametrize("query_shift", [0.0, 0.5])
def test_inner_product_run_of_the_l2_program_is_not_correct(bench_json,
                                                            query_shift):
    """The harness does not tell the program the metric: an L2-only
    program serving an inner-product configuration returns L2 distances
    and L2 neighbours, and the check sees both."""
    cfg = tiny_config("float32", "ip")
    cfg["generator"]["query_shift"] = query_shift
    out = run_tiny(cfg, bench_json)
    assert not out["correct"]
    c = out["checks"]
    assert c["dist_gap"]["value"] > c["dist_gap"]["limit"]
    assert c["recall"]["value"] < c["recall"]["limit"]
