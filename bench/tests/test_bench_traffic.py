"""The traffic generators: reproducible from the seed, the same work for
every seed, and the rates, bursts and lengths their mix files state."""

import numpy as np
import pytest

from bench import traffic


def build(name, seed, rate=4.0, window=40.0):
    mix = traffic.load_mix(name)
    return mix, traffic.build(mix, seed=seed, vocab=1000,
                              warmup_s=mix["warmup_s"], window_s=window,
                              rate=rate)


def window_of(mix, t, window=40.0):
    w0 = mix["warmup_s"]
    return [a for a in t.arrivals if w0 <= a.t < w0 + window]


@pytest.mark.parametrize("name", ["chat-poisson", "chat-burst"])
def test_same_seed_same_schedule_other_seed_same_work(name):
    seed = 2 ** 40 + 3
    mix, a = build(name, seed)
    _, b = build(name, seed)
    _, c = build(name, seed + 1)
    key = [(x.t, x.prompt_len, x.out_len) for x in a.arrivals]
    assert key == [(x.t, x.prompt_len, x.out_len) for x in b.arrivals]
    assert np.array_equal(a.tokens(5, 100), b.tokens(5, 100))
    assert key != [(x.t, x.prompt_len, x.out_len) for x in c.arrivals]
    wa, wc = window_of(mix, a), window_of(mix, c)
    assert abs(len(wa) - len(wc)) <= 2
    if name == "chat-poisson":
        assert sorted(x.prompt_len for x in wa) == sorted(
            x.prompt_len for x in wc)
        assert sum(x.out_len for x in wa) == sum(x.out_len for x in wc)


def test_poisson_rate_and_lengths():
    mix, t = build("chat-poisson", 11, rate=4.0, window=2000.0)
    w = window_of(mix, t, 2000.0)
    assert len(w) == 8000
    p = np.array([x.prompt_len for x in w])
    o = np.array([x.out_len for x in w])
    assert p.min() >= 16 and p.max() <= 1024
    assert o.min() >= 8 and o.max() <= 256
    # prompts come from a palette of 64 lognormal draws, used evenly
    assert len(set(p)) <= mix["prompt"]["palette"]
    assert len(set(x.prompt_len for x in t.arrivals)) <= 64
    assert np.median(p) == pytest.approx(256, rel=0.35)
    assert np.median(o) == pytest.approx(128, rel=0.06)
    # lognormal quartiles: median * exp(+-0.674 sigma)
    assert np.percentile(o, 75) == pytest.approx(128 * np.exp(0.674 * 0.7),
                                                 rel=0.08)
    gaps = np.diff([x.t for x in w])
    assert gaps.mean() == pytest.approx(0.25, rel=0.02)
    assert gaps.std() == pytest.approx(0.25, rel=0.1)       # exponential


def test_burst_sizes_and_mean_rate():
    mix, t = build("chat-burst", 12, rate=4.0, window=2000.0)
    w = window_of(mix, t, 2000.0)
    assert len(w) == pytest.approx(8000, abs=8)
    ts = np.array([x.t for x in w])
    # bursts: arrivals closer than their 200 ms spread belong together
    starts = np.concatenate([[True], np.diff(ts) > mix["burst"]["spread_s"]])
    sizes = np.diff(np.concatenate([np.flatnonzero(starts), [len(ts)]]))
    assert sizes.mean() == pytest.approx(mix["burst"]["mean"], rel=0.15)


def test_backlog_keeps_its_depth_and_needs_no_rate():
    mix = traffic.load_mix("chat-backlog")
    t = traffic.build(mix, seed=3, vocab=1000, warmup_s=mix["warmup_s"],
                      window_s=40.0, rate=None)
    assert t.backlog == mix["queued"] == 128
    assert len(t.arrivals) == mix["count"]
    with pytest.raises(ValueError):
        traffic.build(traffic.load_mix("chat-poisson"), seed=3, vocab=10,
                      warmup_s=1.0, window_s=1.0, rate=None)
