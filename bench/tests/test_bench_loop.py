"""CPU rehearsal of the harness loop at smoke size: the open-loop driver
over a real engine, its TTFT and gap arithmetic, the window and drain
rules, prompt replay against the engine's bucket plan, and a whole run
through ``run.execute`` with no compile inside the window."""

import math

import jax
import numpy as np
import pytest

from bench import latency, program, serve_loop, traffic, weights
from bench.tests import smoke

SEED = 2 ** 35 + 9


@pytest.fixture(scope="module")
def driven():
    model = smoke.MODEL
    cfg = program.arch_config(model, check=False)
    params = program.params_fn(cfg, model)(weights.seed_key(SEED))
    eng = program.engine(params, cfg, model["engine"])
    mix = dict(smoke.MIX)
    load = traffic.build(mix, seed=SEED, vocab=model["vocab_size"],
                         warmup_s=mix["warmup_s"], window_s=1.5, rate=8.0)
    w0, w1 = mix["warmup_s"], mix["warmup_s"] + 1.5
    log = serve_loop.OpenLoop(eng, load, (w0, w1)).run()
    return eng, load, log


def test_window_requests_all_finish_and_the_loop_stops_after(driven):
    eng, load, log = driven
    w0, w1 = log.window
    recs = log.in_window()
    assert recs and all(r.done and not r.failed for r in recs)
    assert log.end >= w1
    assert all(len(r.tokens) == r.out_len for r in recs)
    # every request due before the close was submitted, none before due
    for r in log.recs.values():
        if r.arrival < w1:
            assert r.submitted >= r.arrival
    # load was still offered after the close while the window drained
    late = [r for r in log.recs.values()
            if w1 <= r.arrival <= log.end and not math.isnan(r.submitted)]
    assert late or log.end - w1 < 0.5


def test_ttft_and_gap_arithmetic(driven):
    _, _, log = driven
    recs = log.in_window()
    e2e = latency.end_to_end(log)
    ttft = [r.tokens[0] - r.arrival for r in recs]
    assert e2e["ttft_p90_ms"] == pytest.approx(1e3 * np.percentile(ttft, 90))
    gaps = np.concatenate([np.diff(r.tokens) for r in recs])
    assert (gaps >= 0).all()
    assert e2e["itl_p50_ms"] == pytest.approx(1e3 * np.median(gaps))
    assert e2e["itl_p95_ms"] == pytest.approx(1e3 * np.percentile(gaps, 95))
    assert e2e["itl_p99_ms"] == pytest.approx(1e3 * np.percentile(gaps, 99))
    w0, w1 = log.window
    n = sum(1 for r in log.recs.values() for t in r.tokens if w0 <= t < w1)
    assert e2e["output_tokens_per_s"] == pytest.approx(n / (w1 - w0))
    # a token is timestamped no earlier than admission, after arrival
    for r in recs:
        assert r.arrival <= r.submitted <= r.admitted <= r.tokens[0]


def test_prompt_replay_follows_the_bucket_plan(driven):
    eng, _, log = driven
    small = min(eng.prefill_buckets)
    checked = 0
    for r in log.recs.values():
        if not r.tokens:
            continue
        if r.prompt_len >= small:
            assert r.prefill_len <= r.prompt_len
            assert r.replay_ticks == r.prompt_len - r.prefill_len
        else:
            assert r.replay_ticks == 0 and r.prefill_len == small
        checked += 1
    assert checked > 5


def test_percentile_counts_missing_answers_as_late():
    assert latency.percentile([1.0, 2.0, math.inf], 50) == 2.0
    assert latency.percentile([1.0, math.inf, math.inf], 90) == math.inf


def test_whole_run_is_correct_with_nothing_compiled_in_the_window():
    from bench import run
    res = run.execute(smoke.cell(), seed=SEED + 1, seconds=1.0, traced=False,
                      devices=jax.devices(), peaks=smoke.PEAKS,
                      check_model=False)
    assert res["correct"], res["checks"]
    assert res["setup"]["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p50_ms", "itl_p99_ms",
                                   "output_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "checks"


def test_closed_backlog_keeps_the_queue_full():
    model = smoke.MODEL
    cfg = program.arch_config(model, check=False)
    eng = program.engine(program.params_fn(cfg, model)(
        weights.seed_key(SEED)), cfg, model["engine"])
    mix = dict(smoke.MIX, kind="backlog", queued=6, count=400, after_s=0.0)
    load = traffic.build(mix, seed=SEED, vocab=model["vocab_size"],
                         warmup_s=0.3, window_s=0.7, rate=None)
    depth = []
    inner = eng.step

    def step(tick):
        depth.append(eng.scheduler.pending)
        inner(tick)

    eng.step = step
    log = serve_loop.OpenLoop(eng, load, (0.3, 1.0)).run()
    recs = log.in_window()
    assert recs and all(r.done and not r.failed for r in recs)
    # every slot busy and the queue refilled to its depth before each tick
    assert min(depth[1:]) >= mix["queued"] - eng.n_slots
    assert all(r.arrival == r.submitted for r in log.recs.values()
               if not math.isnan(r.submitted))
