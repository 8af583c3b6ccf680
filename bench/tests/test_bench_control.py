"""The output check fails what it has to fail, at smoke size on the CPU.

A control (the reference at float8 in the program's place) reads far
above the program; and a run whose timed path is broken underneath comes
out not correct: a token altered where it is produced, a decode step that
returns its cache unchanged, half of the batch left out.  (A cell on one
chip has no exchange between chips to leave out.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, program, run, serve_loop, traffic, weights
from bench.tests import smoke

LONG = dict(smoke.MIX, output={"median": 24, "sigma": 0.4, "min": 8,
                               "max": 30})


def _served(seed):
    model = smoke.MODEL
    cfg = program.arch_config(model, check=False)
    eng = program.engine(program.params_fn(cfg, model)(
        weights.seed_key(seed)), cfg, model["engine"])
    load = traffic.build(LONG, seed=seed, vocab=model["vocab_size"],
                         warmup_s=LONG["warmup_s"], window_s=1.0, rate=8.0)
    log = serve_loop.OpenLoop(eng, load, (LONG["warmup_s"],
                                          LONG["warmup_s"] + 1.0)).run()
    served = {rid: np.asarray(r.tokens) for rid, r in eng.results.items()
              if not r.rejected}
    return check.sample(log, served, load, seed, 512, 8)


def test_fp8_control_reads_far_above_the_program():
    prog, ctl = [], []
    for seed in (1, 2, 3):
        picked, prompts, toks = _served(seed)
        r = check.readings(smoke.MODEL, seed, picked, prompts, toks,
                           controls=("fp8",))
        assert r["tokens_checked"] >= 100
        prog.append(r["logit_gap_max"])
        ctl.append(r["fp8_control_gap_max"])
    assert sum(ctl) > 3 * sum(prog), (prog, ctl)
    assert max(prog) < smoke.cell().cell["limits"]["logit_gap_max"]


class _Broken:
    """The engine's decode entry with ``change`` between it and its caller;
    everything else (compile counters) still reads through."""

    def __init__(self, inner, change):
        self.inner, self.change = inner, change

    def __call__(self, params, caches, tok, pos, tbl):
        return self.change(self.inner, params, caches, tok, pos, tbl)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _wrap_decode(eng, change):
    eng._decode = _Broken(eng._decode, change)


def altered_token(eng):
    def change(inner, params, caches, tok, pos, tbl):
        logits, caches = inner(params, caches, tok, pos, tbl)
        bump = jax.nn.one_hot((jnp.argmax(logits[0]) + 1) % logits.shape[-1],
                              logits.shape[-1], dtype=logits.dtype) * 1e4
        return logits.at[0].add(bump), caches
    _wrap_decode(eng, change)


def unchanged_state(eng):
    def change(inner, params, caches, tok, pos, tbl):
        kept = jax.tree.map(jnp.copy, caches)
        logits, _ = inner(params, caches, tok, pos, tbl)
        return logits, kept
    _wrap_decode(eng, change)


def half_batch(eng):
    def change(inner, params, caches, tok, pos, tbl):
        logits, caches = inner(params, caches, tok, pos, tbl)
        half = logits.shape[0] // 2
        return logits.at[half:].set(0.0), caches
    _wrap_decode(eng, change)


@pytest.mark.parametrize("fault", [altered_token, unchanged_state,
                                   half_batch])
def test_a_broken_timed_path_is_not_correct(fault):
    res = run.execute(smoke.cell(mix=LONG), seed=23, seconds=1.0,
                      traced=False, devices=jax.devices(), peaks=smoke.PEAKS,
                      check_model=False, fault=fault)
    assert not res["correct"]
    gap = res["checks"]["logit_gap_max"]
    assert gap["value"] > gap["limit"], res["checks"]


def test_reference_prunes_what_the_program_packs():
    from repro.core.sparsity import compress, decompress

    from bench import reference
    from bench import families
    wd = families.family(smoke.MODEL).ROLES["wd"]
    w = weights.tensor(weights.seed_key(5), wd, 0, (64, 256), 0.1)
    w = w.at[0, :4].set(jnp.asarray([1, -1, 1, 0.5], w.dtype))   # a tie
    got = reference.prune_2_4(w)
    want = decompress(compress(w, 2, 4))
    assert jnp.array_equal(got, want)
    assert jnp.array_equal(got[0, :4], jnp.asarray([1, -1, 0, 0], w.dtype))
