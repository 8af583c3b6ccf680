"""The reduction from trace events to metrics, on constructed traces and on
a small trace recorded here."""

import math

import pytest

from bench import trace as tr
from bench.run import Run


def ev(name, s, e):
    return tr.Event(name, s, e)


OPS = [ev("fusion.1", 0.0, 1.0), ev("nm_spmv_kernel", 0.5, 2.0),
       ev("fusion.2", 3.0, 4.0), ev("paged_gqa_kernel", 6.0, 6.5),
       ev("fusion.3", 9.5, 11.0)]
SPANS = [ev("bench.decode", 0.0, 2.5), ev("bench.admit", 2.5, 5.0),
         ev("bench.decode", 5.0, 7.0), ev("bench.wait", 7.0, 10.0)]


def test_merge_clips_and_joins():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (9.5, 11)], 0, 10) == [
        (0, 2), (3, 4), (9.5, 10)]


def test_busy_and_idle_share():
    window = (0.0, 10.0)
    assert tr.busy_s(OPS, window) == pytest.approx(2.0 + 1.0 + 0.5 + 0.5)
    gaps = tr.idle_gaps(OPS, window)
    assert gaps == [(2.0, 3.0), (4.0, 6.0), (6.5, 9.5)]
    assert sum(e - s for s, e in gaps) + tr.busy_s(OPS, window) \
        == pytest.approx(10.0)


def test_attribution_to_host_spans_and_kernel_matching():
    got = tr.within(OPS, SPANS, "bench.decode", r"nm_spmv|paged_gqa")
    assert {k: [e.name for e in v] for k, v in got.items()} == {
        0: ["nm_spmv_kernel"], 1: ["paged_gqa_kernel"]}
    every = tr.within(OPS, SPANS, "bench.decode")
    assert sorted(e.name for v in every.values() for e in v) == [
        "fusion.1", "nm_spmv_kernel", "paged_gqa_kernel"]
    # an op whose middle falls in no span of that name is nobody's
    assert tr.SpanIndex(SPANS).owner(ev("x", 10.2, 10.4)) is None


def test_breakdown_lists():
    top = tr.top_ops(OPS, (0.0, 10.0), k=3)
    assert top[0] == ["nm_spmv_kernel", 1.5]
    assert {n for n, _ in top[1:]} == {"fusion.1", "fusion.2"}
    # fusion.3 runs past the window: only its part inside counts
    assert dict(tr.top_ops(OPS, (0.0, 10.0)))["fusion.3"] == 0.5
    rows = tr.gaps_by_host(OPS, SPANS, (0.0, 10.0))
    # gaps (2, 3), (4, 6), (6.5, 9.5) by the span at each gap's middle
    assert [(r[0].split(" ")[0], r[1]) for r in rows] == [
        ("bench.wait", 3.0), ("bench.decode", 2.0), ("bench.admit", 1.0)]
    assert sum(r[1] for r in rows) == pytest.approx(6.0)


def test_idle_metric_reads_nothing_without_a_trace():
    from bench.metrics import reader
    run = Run(log=None, stats={}, model={}, peaks={})
    assert reader("device_idle_pct")(run) is None
    run.trace = tr.Trace(ops=[OPS], modules=[[]], spans=SPANS,
                         window=(0.0, 10.0))
    run.busy_s = tr.busy_s(OPS, run.trace.window)
    assert reader("device_idle_pct")(run) == pytest.approx(60.0)


def test_recorded_trace_holds_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    for name in ("bench.admit", "bench.decode", "bench.decode"):
        with jax.profiler.TraceAnnotation(name):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = tr.load(str(tmp_path))
    assert [s.name for s in t.spans] == [
        "bench.admit", "bench.decode", "bench.decode"]
    assert t.window_s > 0 and not math.isnan(t.window_s)
    assert t.ops == []          # no TPU plane on this machine


def test_spans_pair_with_ticks_even_when_the_trace_lost_one():
    class Tick:
        def __init__(self, s, e):
            self.start, self.end = s, e
    ticks = [Tick(0, 1), Tick(2, 5), Tick(6, 6.5)]
    spans = [ev("bench.decode", 10, 11), ev("bench.decode", 12, 15),
             ev("bench.decode", 16, 16.5)]
    got_s, got_t = tr.pair(spans, ticks)
    assert got_t == ticks and got_s == spans
    got_s, got_t = tr.pair(spans[1:], ticks)      # the first span lost
    assert got_t == ticks[1:]
    got_s, got_t = tr.pair(spans[:2], ticks)      # the last span lost
    assert got_t == ticks[:2]


def test_op_names_are_cut_to_name_and_type():
    assert tr.short("%nm_xwt.48 = bf16[128,8192]{1,0:T(8,128)(2,1)S(1)} "
                    "custom-call(bf16[4,128,5504]{2,1,0} %x)") == \
        "%nm_xwt.48 bf16[128,8192]"
    assert tr.short("%while.5 = (s32[]{:T(128)}, bf16[64,1,8192]) while(x)") \
        == "%while.5"
    assert tr.short("jit__lambda(123)") == "jit__lambda(123)"
