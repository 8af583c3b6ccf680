"""``attn_live_blocks_pct``: the share of the fused paged attention grid
that holds context, read from the ``kv_blocks`` and ``table_blocks`` counts
of the engine's ``serve.step`` spans inside the measured window."""

import pytest

from bench import program_spans, serve_loop
from bench.metrics import reader
from bench.run import Run
from repro.serve import trace as recorder

T0 = 100.0                               # perf_counter of the loop's start


def span(name, s, e, sid, **counts):
    return recorder.Span(name, T0 + s, T0 + e, sid, None, 1, None, counts)


def run_of(t0, window=(1.0, 3.0), end=5.0):
    log = serve_loop.Log(recs={}, ticks=[], admissions=[], t0=t0,
                         window=window, end=end)
    return Run(log=log, stats={}, model={}, peaks={})


def test_live_blocks_over_the_window_ticks(monkeypatch):
    counted = [
        span("serve.step", 0.5, 0.6, 1, kv_blocks=80, table_blocks=80),
        span("serve.step", 1.0, 1.1, 2, rows=3, kv_blocks=10,
             table_blocks=80),
        span("serve.step", 1.5, 1.6, 4, rows=3, kv_blocks=30,
             table_blocks=80),
        span("serve.step", 2.5, 2.6, 9, rows=3),   # a tick with no counts
        span("serve.admit", 2.7, 2.8, 10, kv_blocks=80, table_blocks=80),
        span("serve.step", 3.5, 3.6, 11, kv_blocks=80, table_blocks=80),
    ]
    monkeypatch.setattr(program_spans, "recorded", lambda r: counted)
    # only the two counted ticks inside the window: (10 + 30) / (80 + 80)
    assert reader("attn_live_blocks_pct")(run_of(T0)) == pytest.approx(25.0)


def test_ticks_without_counts_read_nothing(monkeypatch):
    plain = [span("serve.step", 1.0 + 0.1 * i, 1.05 + 0.1 * i, i, rows=3)
             for i in range(5)]
    monkeypatch.setattr(program_spans, "recorded", lambda r: plain)
    assert reader("attn_live_blocks_pct")(run_of(T0)) is None


def test_reads_the_recorder_and_nothing_outside_the_loop():
    t0 = -1000.0                        # before any real span, so no other
                                        # test's spans fall in this loop
    recorder.record("serve.step", t0 + 1.2, t0 + 1.3, engine=0, rows=2,
                    kv_blocks=3, table_blocks=12)
    assert reader("attn_live_blocks_pct")(run_of(t0)) == pytest.approx(25.0)
    # a loop no recorded span lies in, and a run with no loop at all
    assert reader("attn_live_blocks_pct")(run_of(-1e9)) is None
    assert program_spans.recorded(Run(log=None, stats={}, model={},
                                      peaks={})) == []
