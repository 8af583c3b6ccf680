"""The readers of the program's own spans (``bench/program_spans.py`` and
the four metrics built on it): on constructed spans and device operations,
and on a smoke run traced here, where a ``serve.step`` mapped from the
recorder must land on its annotation in the trace."""

import math
import shutil
import tempfile
import time

import jax
import pytest

from bench import program, program_spans, serve_loop, traffic, weights
from bench import trace as tr
from bench.latency import percentile
from bench.metrics import reader
from bench.run import Run, _reduce_trace
from bench.tests import smoke
from repro.serve import trace as recorder

SEED = 2 ** 33 + 17
T0 = 100.0                               # perf_counter of the loop's start
SHIFT = 1000.0                           # trace clock less perf_counter


def span(name, s, e, sid, parent=None, rid=None, **counts):
    return recorder.Span(name, T0 + s, T0 + e, sid, parent, 1, rid, counts)


def rec(rid, tokens):
    r = serve_loop.Rec(rid, arrival=1.5, prompt_len=64, out_len=4)
    r.submitted = r.admitted = 1.5
    r.tokens = list(tokens)
    return r


def log_of(recs):
    return serve_loop.Log(recs={r.rid: r for r in recs}, ticks=[],
                          admissions=[], t0=T0, window=(1.0, 3.0), end=5.0)


SPANS = [
    span("serve.step", 0.5, 0.6, 1),                 # before the window
    span("serve.step", 1.0, 1.1, 2, rows=3),
    span("serve.sync", 1.02, 1.09, 3, parent=2),
    span("serve.step", 1.5, 1.6, 4, rows=3),
    span("serve.sync", 1.51, 1.59, 5, parent=4),
    span("serve.admit", 2.0, 2.01, 6, rid=0),
    span("serve.admit", 2.1, 2.13, 7, rid=1),
    span("serve.sync", 2.12, 2.13, 8, parent=7),
]


@pytest.fixture
def constructed(monkeypatch):
    """A run whose recorder holds ``SPANS`` (times in seconds from T0)."""
    recs = [rec(i, [2.0 + i]) for i in range(20)]
    run = Run(log=log_of(recs), stats={}, model={}, peaks={})
    monkeypatch.setattr(program_spans, "recorded", lambda r: SPANS)
    return run


def test_host_time_per_tick_and_per_admission(constructed):
    # ticks: 100 - 70 and 100 - 80 ms; admissions: 10 and 30 - 10 ms
    assert reader("tick_host_ms")(constructed) == pytest.approx(25.0)
    assert reader("admit_host_ms")(constructed) == pytest.approx(15.0)


def test_replay_percentile_counts_prefilled_as_zero_and_missing_as_late(
        constructed, monkeypatch):
    replay = [span("serve.replay", 2.0, 2.0 + 0.1 * i, 20 + i, rid=i,
                   tokens=i) for i in range(1, 15)]
    # a second replay of rid 1 (after a preemption) is not its first
    replay.append(span("serve.replay", 4.0, 4.9, 40, rid=1, tokens=3))
    monkeypatch.setattr(program_spans, "recorded",
                        lambda r: SPANS + replay)
    recs = constructed.log.recs
    recs[19].tokens = []                 # never reached its first token
    want = [0.0] + [0.1 * i for i in range(1, 15)] + [0.0] * 4 + [math.inf]
    got = reader("replay_p90_ms")(constructed)
    assert got == pytest.approx(1e3 * percentile(want, 90))
    recs[18].tokens = []                 # two late of twenty: p90 is late
    assert reader("replay_p90_ms")(constructed) is None


def test_idle_inside_mapped_ticks(constructed):
    run = constructed
    ticks = [serve_loop.Tick(1.0, 1.1, None), serve_loop.Tick(1.5, 1.6, None)]
    decode = [tr.Event("bench.decode", T0 + SHIFT + 1.0, T0 + SHIFT + 1.1),
              tr.Event("bench.decode", T0 + SHIFT + 1.5 + 2e-6,
                       T0 + SHIFT + 1.6)]
    base = T0 + SHIFT
    ops = [tr.Event("a", base + 1.0, base + 1.02),
           tr.Event("b", base + 1.05, base + 1.1),
           tr.Event("c", base + 1.5, base + 1.58)]
    run.trace = tr.Trace(ops=[ops, ops], modules=[[], []], spans=decode,
                         window=(base + 0.99, base + 1.61))
    run.traced_ticks = ticks
    assert program_spans.offset(run) == pytest.approx(T0 + SHIFT - T0 + 1e-6)
    # idle 30 ms in the first tick, 20 ms in the second; the gap between
    # them (1.1 .. 1.5) is no tick's
    assert reader("tick_idle_ms")(run) == pytest.approx(25.0, abs=0.01)


def test_nothing_to_read_gives_nothing():
    log = log_of([rec(0, [2.0])])
    log.t0 = -1e9                        # a loop no recorded span lies in
    run = Run(log=log, stats={}, model={}, peaks={})
    for name in ("tick_host_ms", "admit_host_ms", "replay_p90_ms",
                 "tick_idle_ms"):
        assert reader(name)(run) is None
    assert program_spans.recorded(Run(log=None, stats={}, model={},
                                      peaks={})) == []


@pytest.fixture(scope="module")
def traced_smoke():
    """A smoke run of the open loop over the engine, the first second of its
    window traced as ``bench/run.py`` traces it: every prompt length primed
    before the loop, and no Python tracer.  Without either, a process with
    a cold compile cache spends the traced second compiling admissions
    under the Python tracer, and one or two ticks are left to map by."""
    model = smoke.MODEL
    cfg = program.arch_config(model, check=False)
    params = program.params_fn(cfg, model)(weights.seed_key(SEED))
    eng = program.engine(params, cfg, model["engine"])
    mix = dict(smoke.MIX)
    load = traffic.build(mix, seed=SEED, vocab=model["vocab_size"],
                         warmup_s=mix["warmup_s"], window_s=1.5, rate=8.0)
    program.prime(eng, [a.prompt_len for a in load.arrivals])
    w0 = mix["warmup_s"]
    tdir = tempfile.mkdtemp(prefix="bench-spans-")
    marks = {}

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        marks["trace_on"] = time.perf_counter()

    def stop():
        marks["trace_off"] = time.perf_counter()
        jax.profiler.stop_trace()

    log = serve_loop.OpenLoop(eng, load, (w0, w0 + 1.5),
                              at={w0: start, w0 + 1.0: stop}).run()
    try:
        trace = tr.load(tdir)
        notes = tr.load(tdir, span_prefix="serve.").spans
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    run = Run(log=log, stats=eng.stats(), model=model, peaks=smoke.PEAKS)
    _reduce_trace(run, trace, marks, log)
    return run, notes


def test_mapped_ticks_land_on_their_annotations(traced_smoke):
    run, notes = traced_smoke
    mapped = program_spans.mapped(run, "serve.step")
    steps = [e for e in notes if e.name == "serve.step"]
    # enough ticks that the median offset stands on many pairs
    assert len(mapped) >= 10
    for m in mapped:
        near = min(steps, key=lambda e: abs(e.start - m.start))
        assert abs(near.start - m.start) < 5e-4
        assert abs(near.end - m.end) < 5e-4


def test_replay_spans_match_the_open_loop_and_the_readers_read(traced_smoke):
    run, _ = traced_smoke
    spans = program_spans.recorded(run)
    replay = {s.rid: s.counts["tokens"] for s in spans
              if s.name == "serve.replay"}
    done = [r for r in run.log.recs.values() if r.tokens]
    assert any(r.replay_ticks for r in done)
    for r in done:
        assert replay.get(r.rid, 0) == r.replay_ticks
    for name in ("tick_host_ms", "admit_host_ms", "replay_p90_ms"):
        v = reader(name)(run)
        assert v is not None and v >= 0.0
    # no TPU plane here, so no device idle to read
    assert reader("tick_idle_ms")(run) is None
