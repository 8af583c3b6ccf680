"""The serving engine's host spans (``serve.trace``) on a paged smoke run:
one ``serve.step`` per decode tick with its three phases and its counts of
attention blocks, one ``serve.admit`` per admission, a ``serve.replay`` per
request whose prompt replays through decode ticks, the replayed-token
counter, the bounded ring, and spans of two engines in one process kept
apart."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serve import ServeEngine, SpecConfig, synthetic_request
from repro.serve import trace

BUCKETS = (4, 8)
# prompt lengths: 10 and 13 bucket down to 8 and replay 2 and 5 tokens, 5
# buckets down to 4 and replays 1, 8 is prefilled whole
PLENS = (10, 8, 13, 5)
GENS = (3, 4, 2, 3)


@pytest.fixture(scope="module")
def model():
    cfg = get_config("llama3.2-1b", smoke=True)
    cfg = cfg.replace(sparsity=dataclasses.replace(
        cfg.sparsity, mode="compressed", impl="xla"))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(model):
    cfg, params = model
    return ServeEngine(params, cfg, n_slots=2, max_len=32, kv="paged",
                       block_size=4, prefill_buckets=BUCKETS)


def _requests(cfg):
    rng = np.random.default_rng(3)
    return [synthetic_request(cfg, rng, rid=i, prompt_len=p,
                              max_new_tokens=g)
            for i, (p, g) in enumerate(zip(PLENS, GENS))]


def drive(eng, reqs, positions=None):
    """``ServeEngine.run``'s admit-then-step loop, counting for each request
    the decode ticks before its first token, as the benchmark's open
    loop counts them (a slot that has emitted nothing is replaying its
    prompt).  ``positions``, a list, collects every slot's position as
    each tick starts."""
    for r in reqs:
        eng.submit(r)
    replay_ticks = {r.rid: 0 for r in reqs}
    t = 0
    while eng.scheduler.has_work():
        while True:
            pairs = eng.scheduler.admit(t, fits=lambda r: eng._fits(r, t),
                                        limit=1)
            if not pairs or not eng._admit(pairs[0][0], pairs[0][1], t):
                break
        if eng.active.any():
            for st in eng._slots.values():
                if not st.tokens:
                    replay_ticks[st.req.rid] += 1
            if positions is not None:
                positions.append(eng.pos.copy())
            eng.step(t)
        t += 1
    return replay_ticks


@pytest.fixture(scope="module")
def served(model):
    eng = _engine(model)
    reqs = _requests(model[0])
    replay_ticks = drive(eng, reqs)
    return eng, reqs, replay_ticks, trace.spans(engine=eng.trace_id)


def _children(spans):
    out = {}
    for s in spans:
        if s.parent is not None:
            out.setdefault(s.parent, []).append(s)
    return out


def test_one_step_span_per_tick_with_its_three_phases(served):
    eng, reqs, _, spans = served
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == eng.decode_steps > 0
    kids = _children(spans)
    for s in steps:
        got = kids[s.sid]
        assert [c.name for c in got] == ["serve.prepare", "serve.sync",
                                         "serve.commit"]
        assert all(s.start <= c.start <= c.end <= s.end for c in got)
        assert all(c.engine == eng.trace_id for c in got)
        assert 1 <= s.counts["rows"] <= eng.n_slots
    st = eng.stats()
    assert st["first_tick_s"] == pytest.approx(steps[0].dur)
    assert st["steady_tick_s"] == pytest.approx(
        float(np.median([s.dur for s in steps[1:]])))


def test_step_spans_count_the_attention_blocks_with_context(model):
    """``kv_blocks`` is the table columns holding context, one slot of
    ``pos + 1`` positions at ``ceil((pos + 1) / block_size)``, idle slots
    (position 0) at one; ``table_blocks`` is the whole table."""
    eng = _engine(model)
    positions = []
    drive(eng, _requests(model[0]), positions)
    assert eng.preemptions == 0
    steps = [s for s in trace.spans(engine=eng.trace_id)
             if s.name == "serve.step"]
    assert len(steps) == len(positions) > 0
    bs, width = eng.pool.block_size, eng.pool.table_width
    for s, pos in zip(steps, positions):
        assert s.counts["kv_blocks"] == sum(-(-(int(p) + 1) // bs)
                                            for p in pos)
        assert s.counts["table_blocks"] == eng.n_slots * width == 2 * 8
    assert len({s.counts["kv_blocks"] for s in steps}) > 1


def test_one_admit_span_per_admission_with_its_rid(served):
    eng, reqs, _, spans = served
    admits = [s for s in spans if s.name == "serve.admit"]
    assert sorted(s.rid for s in admits) == [r.rid for r in reqs]
    assert eng.preemptions == 0
    kids = _children(spans)
    for s in admits:
        r = reqs[s.rid]
        bucket = eng._plan(r)[0]
        assert s.counts["positions"] == bucket
        names = [c.name for c in kids[s.sid]]
        # only an admission whose prefill emits the first token reads back
        if bucket == r.prompt_len:
            assert names == ["serve.prefill", "serve.seed", "serve.sync"]
        else:
            assert names == ["serve.prefill", "serve.seed"]


def test_replay_spans_count_the_tokens_fed_by_decode(served):
    eng, reqs, replay_ticks, spans = served
    replay = {s.rid: s for s in spans if s.name == "serve.replay"}
    admit_end = {s.rid: s.end for s in spans if s.name == "serve.admit"}
    for r in reqs:
        bucket = eng._plan(r)[0]
        if bucket == r.prompt_len:       # prefill emitted the first token
            assert r.rid not in replay and replay_ticks[r.rid] == 0
            continue
        s = replay[r.rid]
        assert s.counts["tokens"] == r.prompt_len - bucket \
            == replay_ticks[r.rid]
        assert s.start == admit_end[r.rid] and s.end > s.start
    assert sum(s.counts["tokens"] for s in replay.values()) \
        == eng.stats()["replayed_prompt_tokens"] \
        == sum(replay_ticks.values()) == 2 + 5 + 1


def test_speculative_ticks_count_the_same_replay(model):
    """Under ``spec=`` a replaying prompt rides the verify span k+1 tokens
    at a time; the tokens it replays are still ``prompt_len - bucket``."""
    cfg, params = model
    eng = ServeEngine(params, cfg, n_slots=2, max_len=32, kv="paged",
                      block_size=4, prefill_buckets=BUCKETS,
                      spec=SpecConfig(k=2, draft="skip"))
    reqs = _requests(cfg)
    eng.run(reqs)
    spans = trace.spans(engine=eng.trace_id)
    replay = {s.rid: s.counts["tokens"] for s in spans
              if s.name == "serve.replay"}
    assert replay == {r.rid: r.prompt_len - eng._plan(r)[0] for r in reqs
                      if eng._plan(r)[0] < r.prompt_len}
    assert eng.stats()["replayed_prompt_tokens"] == sum(replay.values())
    steps = [s for s in spans if s.name == "serve.step"]
    kids = _children(spans)
    assert steps and all(
        {c.name for c in kids[s.sid]} <= {"serve.prepare", "serve.sync",
                                         "serve.commit"}
        and "serve.sync" in {c.name for c in kids[s.sid]} for s in steps)


def test_ring_drops_its_oldest_spans_at_capacity(monkeypatch):
    monkeypatch.setattr(trace, "CAPACITY", 4)
    trace.reset()
    try:
        for i in range(10):
            trace.record("x", float(i), float(i) + 0.5, engine=0, rid=i)
        assert [s.rid for s in trace.spans()] == [6, 7, 8, 9]
        assert trace.dropped() == 6
        assert [s.rid for s in trace.spans(7.0, 9.0)] == [7, 8]
    finally:
        monkeypatch.undo()
        trace.reset()
    assert trace.dropped() == 0 and trace.spans() == []


def test_two_engines_keep_their_spans_apart(model):
    a, b = _engine(model), _engine(model)
    assert a.trace_id != b.trace_id
    cfg = model[0]
    drive(a, _requests(cfg)[:2])
    drive(b, _requests(cfg))
    for eng in (a, b):
        own = trace.spans(engine=eng.trace_id)
        assert len([s for s in own if s.name == "serve.step"]) \
            == eng.decode_steps
        assert {s.engine for s in own} == {eng.trace_id}
    # a's replays are its own requests' (rid 0 only: rid 1 is prefilled)
    assert [s.rid for s in trace.spans(engine=a.trace_id,
                                       name="serve.replay")] == [0]
    assert a.stats()["replayed_prompt_tokens"] == 2
    assert b.stats()["replayed_prompt_tokens"] == 8


def test_a_span_nests_under_the_open_one_and_inherits_its_engine():
    with trace.span("outer", engine=77, rid=5, n=1) as outer:
        with trace.span("inner") as inner:
            inner.counts["k"] = 2
    got = {s.name: s for s in trace.spans(engine=77)}
    assert got["inner"].parent == outer.sid and got["outer"].parent is None
    assert got["inner"].counts == {"k": 2} and got["outer"].rid == 5
    assert got["outer"].start <= got["inner"].start <= got["inner"].end \
        <= got["outer"].end
