"""The reduction of the program's spans and stage scopes, on hand-made
events, hand-made HLO and counters, on a tiny CPU run, and on a trace
recorded on the chip (``fixtures/sift1m_batch_spans.json.gz``)."""

import numpy as np
import pytest

from bench import run, spans, trace
from bench.conftest import BENCH, TINY

DEV = "/device:TPU:0"
HOST = "/host:CPU"
FIXTURE = BENCH / "fixtures" / "sift1m_batch_spans.json.gz"


def op(name, start, dur, scope="", line=trace.OPS_LINE):
    return spans.Event(DEV, line, name, float(start), float(dur), scope)


def host(name, start, end):
    return spans.Event(HOST, "python3", name, float(start),
                       float(end - start))


# busy [100, 600) beam_search, [600, 700) rerank, [800, 900) no scope;
# idle [700, 800) under serve.block, [900, 1100) mostly under serve.finish
HAND = [host(trace.WINDOW, 100, 1100),
        op("while.1", 100, 500, "beam_search"),
        op("fusion.2", 150, 100, "beam_search/visited"),
        op("fusion.3", 600, 100, "rerank"),
        op("copy.4", 800, 100),
        host("serve.flush", 0, 120), host("serve.flush", 300, 340),
        host("serve.dispatch", 305, 335),
        host("serve.finish", 650, 1000), host("serve.block", 700, 950)]


def ctx_of(events=None, counters=None):
    ctx = run.Context(config={}, peaks={}, setup_s=0, window_s=1,
                      answered=None, latency_s=None, good=None, recall=0,
                      flush_sizes=[], buckets=(64,), events=events)
    if counters is not None:
        ctx.counters = counters
    return ctx


@pytest.mark.parametrize("stack,scope", [
    ("jit(search_step)/cluster_filter/jit(cluster_filter)/top_k",
     "cluster_filter"),
    ("jit(search_step)/route_lanes/jit(route_lanes)/jit(argsort)/sort",
     "route_lanes"),
    ("jit(search_step)/vmap(prepare_lanes)/gather", "prepare_lanes"),
    ("jit(search_step)/vmap(beam_search)/vmap(jit(beam_search_lane))/while",
     "beam_search"),
    ("jit(search_step)/vmap(beam_search)/vmap(jit(beam_search_lane))"
     "/while/body/visited/or", "beam_search/visited"),
    ("jit(search_step)/vmap(beam_search)/vmap(jit(beam_search_lane))"
     "/while/body/rank/expand/gather", "beam_search/expand"),
    ("jit(search_step)/rerank/jit(rerank)/jit(topk_select)/pallas_call",
     "rerank"),
    ("jit(merge_topk)/sort", "merge_topk"),
    ("jit(search_step)/jit(clip)/pjit", ""),
])
def test_scope_of_a_name_stack(stack, scope):
    assert spans.scope_of(stack) == scope


HLO = """HloModule jit_f, is_scheduled=true

%fused_computation (p: s32[4]) -> s32[4] {
  %p = s32[4]{0} parameter(0)
  ROOT %or.1 = s32[4]{0} or(%p, %p), metadata={op_name="jit(f)/vmap(beam_search)/while/body/visited/or"}
}

%body (s: s32[4]) -> s32[4] {
  %s = s32[4]{0} parameter(0)
  %fusion.1 = s32[4]{0} fusion(%s), kind=kLoop, calls=%fused_computation
  ROOT %copy.2 = s32[4]{0} copy(%fusion.1)
}

%cond (c: s32[4]) -> pred[] {
  %c = s32[4]{0} parameter(0)
  ROOT %constant.3 = pred[] constant(true)
}

ENTRY %main (x: s32[4]) -> s32[4] {
  %x = s32[4]{0} parameter(0)
  %copy-start.4 = (s32[4]{0}, s32[4]{0}, u32[]) copy-start(%x)
  %copy-done.5 = s32[4]{0} copy-done(%copy-start.4)
  %while.6 = s32[4]{0} while(%copy-done.5), condition=%cond, body=%body, metadata={op_name="jit(f)/vmap(beam_search)/vmap(jit(beam_search_lane))/while"}
  ROOT %negate.7 = s32[4]{0} negate(%while.6), metadata={op_name="jit(f)/rerank/neg"}
}
"""


def test_hlo_scopes_fill_what_the_compiler_left_bare():
    s = spans.hlo_scopes(HLO)
    assert s["while.6"] == "beam_search"
    assert s["negate.7"] == "rerank"
    # a fusion without metadata: the scope of what it calls
    assert s["fusion.1"] == "beam_search/visited"
    # a copy the compiler put in: its operand's scope, else its users'
    assert s["copy.2"] == "beam_search/visited"
    assert s["copy-done.5"] == s["copy-start.4"] == "beam_search"
    # nothing in the loop condition has a scope: the while's
    assert s["constant.3"] == "beam_search"


def test_an_op_inside_a_scoped_op_takes_its_scope():
    got = spans._inherit_scopes([op("while.1", 0, 100, "beam_search"),
                                 op("sort.2", 10, 20),
                                 op("copy.3", 150, 10)])
    assert [e.scope for e in got] == ["beam_search", "beam_search", ""]


def test_stage_shares_and_span_lengths_on_hand_made_events():
    assert spans.stage_share(HAND, "beam_search") == pytest.approx(
        100 * 500 / 700)
    assert spans.stage_share(HAND, "rerank") == pytest.approx(100 * 100 / 700)
    assert spans.stage_share(HAND, "beam_search/visited") == pytest.approx(
        100 * 100 / 700)
    # only the flush wholly inside the window
    assert spans.span_ms(HAND, "serve.flush") == pytest.approx(40e-6)
    assert spans.span_ms(HAND, "serve.nap") is None
    assert [e.name for e in spans.host_spans(HAND, lo=100, hi=1100)] == [
        "serve.flush", "serve.dispatch", "serve.finish", "serve.block"]


def test_idle_gaps_name_the_host_span_and_keep_their_lengths():
    ops = trace.device_ops(HAND)[DEV]
    gaps = spans.idle_gaps(ops, HAND, 100, 1100)
    assert gaps == [["after copy.4; host in serve.finish",
                     pytest.approx(200e-9)],
                    ["after fusion.3; host in serve.block",
                     pytest.approx(100e-9)]]
    # the same gaps, the same lengths, as trace.idle_gaps finds them
    plain = trace.idle_gaps(ops, 100, 1100)
    assert [g[1] for g in plain] == [g[1] for g in gaps]
    assert [g[0].split(";")[0] for g in plain] == \
        [g[0].split(";")[0] for g in gaps]
    assert spans.idle_gaps(ops, [], 100, 1100)[0][0] == \
        "after copy.4; no program span"


def test_top_ops_carry_their_scope():
    ops = trace.device_ops(HAND)[DEV]
    assert spans.top_ops(ops, 2) == [
        ["while.1 [beam_search]", pytest.approx(500e-9)],
        ["fusion.2 [beam_search/visited]", pytest.approx(100e-9)]]
    assert spans.top_ops(ops)[-1][0] == "copy.4 [no scope]"


def test_readers_on_hand_made_events_and_counters():
    ctx = ctx_of(HAND, {"flushes": 4, "lane_slots": 4096, "live_lanes": 2048,
                        "hops": 100_000, "slot_hops": 224_000,
                        "dropped_lanes": 0})
    assert run.load_reader("beam_share.batch")(ctx) == pytest.approx(
        100 * 500 / 700)
    assert run.load_reader("rerank_share.batch")(ctx) == pytest.approx(
        100 * 100 / 700)
    assert run.load_reader("dispatch_ms.batch")(ctx) == pytest.approx(40e-6)
    assert run.load_reader("live_lane_share.batch")(ctx) == 50.0
    assert run.load_reader("served_lockstep_waste.batch")(ctx) == 2.24


def test_readers_read_nothing_where_the_run_has_no_spans_or_counters():
    """What ``trace.load_events`` keeps (no ``serve.*`` span, no scope)
    and a ``Context`` without ``counters``: every reader gives None."""
    plain = [trace.Event(*e[:5]) for e in HAND
             if not e.name.startswith(spans.SPAN_PREFIX)]
    for name in ("beam_share", "rerank_share", "dispatch_ms",
                 "live_lane_share", "served_lockstep_waste"):
        assert run.load_reader(name + ".batch")(ctx_of(plain)) is None, name


# ---------------------------------------------------------------------------
# a tiny run on the CPU: the spans come back through load_events, and the
# serving executable's ops map to stage scopes through the same lookup
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_topo():
    import jax
    from repro.core import compact_index, engine
    from bench import corpus
    key = corpus.seed_key(7)
    mix = corpus.Mixture.from_config(TINY)
    x = np.asarray(corpus.make_corpus(key, n=TINY["n"], dim=TINY["dim"],
                                      mix=mix))
    pool = np.asarray(corpus.make_queries(key, n=256, dim=TINY["dim"],
                                          mix=mix))
    eng = engine.PIMCQGEngine.build(
        jax.random.fold_in(key, 2), x,
        compact_index.IndexConfig(**TINY["index"]),
        engine.SearchConfig(**TINY["search"]))
    topo = run.topology_config(TINY, {"topology": {"buckets": [64]}}
                               ).build(eng)
    topo.warm()
    return eng, topo, pool


def test_load_events_keeps_the_program_spans(tiny_topo):
    eng, topo, pool = tiny_topo
    tracer = trace.WindowTracer(0.0, 60.0, grace_s=0.0)
    rep = topo.run(pool, ticker=tracer)
    tracer.close()
    ev = spans.load_events(tracer.dir)
    flushes = spans.host_spans(ev, "serve.flush")
    assert len(flushes) == rep.n_flushes
    assert len(spans.host_spans(ev, "serve.finish")) == rep.n_flushes
    assert spans.span_ms(ev, "serve.flush") > 0
    assert trace.window_ns(ev) is not None


def test_serving_ops_map_to_every_stage(tiny_topo):
    eng, topo, pool = tiny_topo
    scopes = spans.hlo_scopes(spans.search_step_hlo(eng, 64))
    got = {s for s in scopes.values() if s}
    want = set(spans.STAGES) - {"merge_topk"}
    assert want <= {s.split("/")[0] for s in got}
    assert {"beam_search/" + b for b in spans.BEAM_STAGES} <= got


# ---------------------------------------------------------------------------
# a trace recorded on the chip: 500 ms of sift1m batch serving with the
# program's spans, its ops scoped through the executable's HLO
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip():
    return spans.read_events(FIXTURE)


def test_chip_trace_splits_the_step_by_stage(chip):
    beam = spans.stage_share(chip, "beam_search")
    rerank = spans.stage_share(chip, "rerank")
    # the chip read 99.70 and 0.24
    assert 95 <= beam <= 100
    assert 0 < rerank < 3
    assert beam + rerank <= 100
    # the hand-read parts of the loop are the loop's
    parts = [spans.stage_share(chip, "beam_search/" + b)
             for b in spans.BEAM_STAGES]
    assert all(p > 0 for p in parts) and sum(parts) < beam


def test_chip_trace_every_top_op_has_a_stage(chip):
    lo, hi = trace.window_ns(chip)
    ops = trace.device_ops(chip)["/device:TPU:0"]
    top = spans.top_ops(ops, 10)
    assert top[0][0].startswith("while") and "[beam_search]" in top[0][0]
    assert not any("[no scope]" in name for name, _ in top)


def test_chip_trace_idle_time_falls_under_program_spans(chip):
    lo, hi = trace.window_ns(chip)
    ops = trace.device_ops(chip)["/device:TPU:0"]
    gaps = spans.idle_gaps(ops, chip, lo, hi, n=None)
    named = sum(s for label, s in gaps if "host in serve." in label)
    assert gaps and named >= 0.9 * sum(s for _, s in gaps)
    # the gaps are the ones the plain reducer finds
    assert [g[1] for g in gaps[:10]] == \
        [g[1] for g in trace.idle_gaps(ops, lo, hi)]


def test_chip_trace_flush_spans_are_short(chip):
    assert 0.1 <= spans.span_ms(chip, "serve.flush") <= 3
    ctx = ctx_of(chip)
    step = run.load_reader("step_ms.batch")(ctx)
    assert run.load_reader("dispatch_ms.batch")(ctx) < step / 4
