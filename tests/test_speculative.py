"""Self-speculative decoding test net (PR 9).

Claim hierarchy, weakest to strongest:

  1. **draft views are views** — ``models.make_draft`` shares every
     non-linear leaf by reference with the serving pool (zero extra weight
     storage) and ``nm_rerank`` keeps exactly the top-``keep`` magnitudes
     per group with indices re-sorted ascending (the compressed-format
     invariant the nm_spmv route relies on).
  2. **verify == sequential decode** — ``models.verify_step`` over a
     [tok, d1..dk] span produces bitwise-identical logits to k+1 sequential
     ``decode_step`` calls on the same paged pool (gather and fused reads),
     which is the whole basis of the token-identity guarantee.
  3. **rollback is safe** — ``BlockPool.rollback`` after a k-token append
     preserves ``check_invariants`` under property-tested churn, refuses to
     free shared blocks, and a rolled-back slot's next decode reads exactly
     the KV a never-appended oracle slot reads.
  4. **engine end-to-end** — ``ServeEngine(spec=SpecConfig(...))`` emits
     bitwise-identical tokens to the non-speculative paged engine across
     dense (llama), windowed/softcap (gemma), and MLA+MoE (deepseek)
     families, in strictly fewer target decode steps; per-request ``spec``
     overrides mix drafting and plain slots in one tick; a spec-configured
     engine with every request opted out matches the spec=None engine
     counter-for-counter (provably zero-cost when disabled).

Plus the donation check: the jitted decode step donates its cache buffers
(``is_deleted`` on the input pool after a step — no per-tick KV copy).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                     # minimal env: keep the deterministic
    from conftest import given, settings, st   # tests, skip the property ones

from repro.configs import get_config
from repro.core.sparse_matmul import nm_rerank
from repro.models import (decode_step, init_model, make_draft, prefill,
                          verify_step, weight_stream_bytes)
from repro.serve import (BlockPool, Request, ServeEngine, SpecConfig,
                         synthetic_request)
from repro.serve.speculative import accept_greedy

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = get_config(arch, smoke=True)
        cfg = cfg.replace(sparsity=dataclasses.replace(
            cfg.sparsity, mode="compressed", impl="xla"))
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        _MODELS[arch] = (cfg, params)
    return _MODELS[arch]


def _ragged(cfg, plens, gens, seed=9):
    rng = np.random.default_rng(seed)
    return [synthetic_request(cfg, rng, rid=i, prompt_len=p,
                              max_new_tokens=g)
            for i, (p, g) in enumerate(zip(plens, gens))]


# ------------------------------------------------------------- draft views

def test_nm_rerank_keeps_top_magnitudes_sorted():
    vals = jnp.asarray([[3.0, -7.0, 1.0, 5.0]])        # one 4-wide group
    idx = jnp.asarray([[2, 0, 5, 7]], jnp.int32)
    rv, ri = nm_rerank(vals, idx, n=4, m=8, keep=2)
    # top-2 by |value| are -7.0 (idx 0) and 5.0 (idx 7), re-sorted by index
    np.testing.assert_array_equal(np.asarray(rv), [[-7.0, 5.0]])
    np.testing.assert_array_equal(np.asarray(ri), [[0, 7]])


def test_nm_rerank_stacked_and_batched():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.standard_normal((3, 4, 8)), jnp.float32)
    idx = jnp.asarray(np.tile(np.arange(8), (3, 4, 1)),
                      jnp.int32)         # ascending over every block's slots
    rv, ri = nm_rerank(vals, idx, n=2, m=4, keep=1)
    assert rv.shape == (3, 4, 4) and ri.shape == (3, 4, 4)
    # each kept value is the max-|.| of its block's 2 slots (slot-planar:
    # entry s * 4 + b is slot s of block b)
    g = np.abs(np.asarray(vals).reshape(3, 4, 2, 4))
    np.testing.assert_array_equal(np.abs(np.asarray(rv)), g.max(-2))


def test_nm_rerank_validates():
    vals = jnp.zeros((2, 8))
    idx = jnp.zeros((2, 8), jnp.int32)
    with pytest.raises(ValueError):
        nm_rerank(vals, idx, n=2, m=4, keep=2)        # keep must be < n
    with pytest.raises(ValueError):
        nm_rerank(vals, idx, n=3, m=4, keep=1)        # 8 % 3 != 0


@pytest.mark.parametrize("arch,kind", [("llama3.2-1b", "rerank"),
                                       ("llama3.2-1b", "skip"),
                                       ("deepseek-v2-lite-16b", "skip")])
def test_make_draft_shares_storage(arch, kind):
    """Every draft leaf is either the target's own array (shared by
    reference — zero extra bytes) or a strictly smaller derived view."""
    cfg, params = _model(arch)
    dp, dcfg, cache_idx = make_draft(params, cfg, kind=kind)
    target_ids = {id(l) for l in jax.tree_util.tree_leaves(params)}
    shared = derived = 0
    for leaf in jax.tree_util.tree_leaves(dp):
        if id(leaf) in target_ids:
            shared += 1
        else:
            derived += 1
    assert shared > 0, "draft view must share leaves with the target"
    ds = weight_stream_bytes(dp, dcfg)
    ts = weight_stream_bytes(params, cfg)
    assert ds["stream_bytes"] < ts["stream_bytes"], \
        "draft view must stream fewer bytes per step than the target"
    if kind == "skip":
        assert cache_idx is not None and cache_idx.ndim == 1
        assert dcfg.n_layers == len(cache_idx) < cfg.n_layers
    else:
        assert cache_idx is None
        assert dcfg.sparsity.n == 1 and derived > 0


def test_make_draft_rejects_bad_combos():
    cfg, params = _model("llama3.2-1b")
    with pytest.raises(ValueError, match="compressed"):
        dense_cfg = cfg.replace(sparsity=dataclasses.replace(
            cfg.sparsity, mode="srste"))
        make_draft(params, dense_cfg, kind="rerank")
    with pytest.raises(ValueError, match="stride"):
        make_draft(params, cfg, kind="skip", stride=1)
    with pytest.raises(ValueError, match="kind"):
        make_draft(params, cfg, kind="nope")
    gcfg, gparams = _model("gemma2-9b")
    with pytest.raises(ValueError, match="plain stacked"):
        make_draft(gparams, gcfg, kind="skip")    # local/global pairs family


def test_spec_config_validates():
    with pytest.raises(ValueError):
        SpecConfig(k=0)
    with pytest.raises(ValueError):
        SpecConfig(draft="tree")
    with pytest.raises(ValueError):
        SpecConfig(stride=1)


def test_accept_greedy_counts_matching_prefix():
    drafts = np.asarray([[5, 6, 7], [5, 9, 7], [1, 2, 3]])
    va = np.asarray([[5, 6, 7, 8], [5, 6, 7, 8], [9, 2, 3, 4]])
    np.testing.assert_array_equal(accept_greedy(drafts, va), [3, 1, 0])


# ------------------------------------------- verify == sequential (bitwise)

@pytest.mark.parametrize("arch,attn", [("llama3.2-1b", "gather"),
                                       ("llama3.2-1b", "fused"),
                                       ("gemma2-9b", "gather"),
                                       ("deepseek-v2-lite-16b", "gather")])
def test_verify_step_bitwise_equals_sequential_decode(arch, attn):
    """The token-identity bedrock: one k+1-wide verify forward must produce
    the logits of k+1 sequential decode steps over the same paged pool —
    span K/V writes, position masking, and the s>1 attention branches all
    collapse to the s==1 path.  Equal to f32 rounding, not bit for bit:
    XLA's CPU dot may order a reduction over a [B, k+1] span differently
    from the same reduction over [B, 1]."""
    cfg, params = _model(arch)
    rng = np.random.default_rng(0)
    B, plen, k = 2, 6, 3
    pool = BlockPool(cfg, B, 24, 4)
    pos0 = np.zeros(B, np.int32)
    tok0 = np.zeros(B, np.int32)
    for s in range(B):
        prompt = rng.integers(0, cfg.vocab, size=plen)
        assert pool.alloc(s, pool.blocks_for(plen))
        logits, pf = prefill(params, cfg,
                             {"tokens": jnp.asarray(prompt)[None]})
        pool.seed(s, pf, plen)
        pos0[s] = plen
        tok0[s] = int(jnp.argmax(logits[0]))
    for s in range(B):
        assert pool.ensure(s, plen + k)
    tbl = pool.device_table()
    tok = jnp.asarray(tok0)
    pos = jnp.asarray(pos0)
    c = pool.caches
    seq_toks, seq_logits = [], []
    for i in range(k + 1):
        lg, c = decode_step(params, cfg, c, tok, pos + i, tbl, attn_impl=attn)
        seq_logits.append(lg)
        tok = jnp.argmax(lg, -1).astype(jnp.int32)
        seq_toks.append(np.asarray(tok))
    span = jnp.concatenate([jnp.asarray(tok0)[:, None],
                            jnp.stack(seq_toks[:k], 1)], 1)
    vlg, _ = verify_step(params, cfg, pool.caches, span, jnp.asarray(pos0),
                         tbl, attn_impl=attn)
    np.testing.assert_allclose(np.asarray(vlg),
                               np.asarray(jnp.stack(seq_logits, 1)),
                               rtol=1e-5, atol=1e-5)


def test_rolled_back_slot_reads_oracle_kv():
    """Write a k-wide speculative span, roll all of it back, decode the
    token the oracle would have decoded: logits must be bitwise equal to a
    pool that never speculated — stale span KV past the committed position
    is invisible (masked until overwritten)."""
    cfg, params = _model("llama3.2-1b")
    rng = np.random.default_rng(3)
    plen, k = 6, 3
    prompt = rng.integers(0, cfg.vocab, size=plen)

    def fresh_pool():
        pool = BlockPool(cfg, 1, 24, 4)
        assert pool.alloc(0, pool.blocks_for(plen))
        logits, pf = prefill(params, cfg,
                             {"tokens": jnp.asarray(prompt)[None]})
        pool.seed(0, pf, plen)
        return pool, int(jnp.argmax(logits[0]))

    spec, tok = fresh_pool()
    assert spec.ensure(0, plen + k)
    junk = jnp.asarray(rng.integers(0, cfg.vocab, (1, k + 1)), jnp.int32)
    _, spec.caches = verify_step(params, cfg, spec.caches, junk,
                                 jnp.asarray([plen]), spec.device_table())
    spec.rollback(0, plen)               # reject the whole junk span
    spec.check_invariants(active_pos={0: plen - 1})
    # the span's blocks past the kept boundary are back on the free heap
    assert len(spec._owned[0]) == spec.blocks_for(plen)

    oracle, _ = fresh_pool()
    targs = (jnp.asarray([tok]), jnp.asarray([plen]))
    sl, _ = decode_step(params, cfg, spec.caches, *targs,
                        spec.device_table())
    ol, _ = decode_step(params, cfg, oracle.caches, *targs,
                        oracle.device_table())
    np.testing.assert_array_equal(np.asarray(sl), np.asarray(ol))


# --------------------------------------------------------- rollback safety

def _pool(n_slots=2, max_len=16, block_size=4, n_blocks=None):
    cfg, _ = _model("llama3.2-1b")
    return BlockPool(cfg, n_slots, max_len, block_size, n_blocks)


def test_rollback_frees_span_tail():
    p = _pool(n_slots=1)
    assert p.alloc(0, 4)                 # backs positions [0, 16)
    free_before = p.free_blocks
    p.rollback(0, 6)                     # keep blocks_for(6) == 2
    assert len(p._owned[0]) == 2
    assert p.free_blocks == free_before + 2
    p.check_invariants(active_pos={0: 5})
    p.rollback(0, 6)                     # idempotent at the same position
    assert len(p._owned[0]) == 2


def test_rollback_refuses_shared_blocks():
    p = _pool(n_slots=2)
    assert p.alloc(0, 3)
    p.share(1, p._owned[0][:3])          # slot 1 names slot 0's blocks
    with pytest.raises(ValueError, match="refcount"):
        p.rollback(1, 0)
    p.check_invariants()


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 2), st.integers(0, 1),
                          st.integers(0, 15)), max_size=40))
def test_invariants_hold_under_append_rollback_churn(ops):
    """Random seed/append-span/rollback/retire sequences: the table never
    exposes a freed block and every kept position stays backed."""
    p = _pool(n_slots=2, max_len=16, block_size=4, n_blocks=9)
    pos = {}
    for kind, slot, arg in ops:
        if kind == 0 and slot not in pos:       # admit
            n = arg % 8 + 1
            if p.alloc(slot, p.blocks_for(n)):
                pos[slot] = n
        elif kind == 1 and slot in pos:         # speculative span + rollback
            span_end = min(pos[slot] + 3, p.max_len)
            if p.ensure(slot, span_end - 1):
                commit = pos[slot] + arg % (span_end - pos[slot] + 1)
                p.rollback(slot, commit)
                pos[slot] = max(commit, 1)
        elif kind == 2 and slot in pos:         # retire
            p.free(slot)
            del pos[slot]
        p.check_invariants(active_pos={s: n - 1 for s, n in pos.items()})


# --------------------------------------------------------- engine identity

_SPEC_FAMS = [("llama3.2-1b", "skip"),          # dense GQA
              ("gemma2-9b", "rerank"),          # windowed/softcap pairs
              ("deepseek-v2-lite-16b", "skip")]  # MLA + MoE


@pytest.mark.parametrize("arch,draft", _SPEC_FAMS)
def test_spec_tokens_match_oracle(arch, draft):
    """The acceptance criterion: speculative greedy decode is bitwise
    token-identical to the non-speculative paged engine on a mixed ragged
    trace, in strictly fewer target decode steps.  n_slots=2, k=3 keeps the
    MoE verify batch inside the expert-capacity floor (no drops) so the
    coupled families compare exactly."""
    cfg, params = _model(arch)
    reqs = _ragged(cfg, plens=[6, 11, 4, 7], gens=[8, 6, 9, 7], seed=7)
    kw = dict(n_slots=2, max_len=24, kv="paged", block_size=4)
    oracle_eng = ServeEngine(params, cfg, **kw)
    oracle = oracle_eng.run([dataclasses.replace(r) for r in reqs])
    eng = ServeEngine(params, cfg, **kw, spec=SpecConfig(k=3, draft=draft),
                      debug_invariants=True)
    res = eng.run([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(oracle[r.rid].tokens, res[r.rid].tokens,
                                      err_msg=f"{arch} rid={r.rid}")
    s, so = eng.stats(), oracle_eng.stats()
    assert s["decode_steps"] < so["decode_steps"]
    assert s["spec_steps_saved"] > 0
    assert s["spec_accepted"] <= s["spec_proposed"]
    eng.pool.check_invariants(active_pos={})


def test_per_request_spec_override_mixes_in_one_tick():
    """Request.spec=False slots ride the plain forward while drafting slots
    verify in the same tick — tokens still match the oracle."""
    cfg, params = _model("llama3.2-1b")
    reqs = _ragged(cfg, plens=[6, 6, 5, 8], gens=[8, 8, 7, 6], seed=5)
    for r in reqs[::2]:
        r.spec = False                   # half the traffic opts out
    kw = dict(n_slots=2, max_len=24, kv="paged", block_size=4)
    oracle = ServeEngine(params, cfg, **kw).run(
        [dataclasses.replace(r) for r in reqs])
    eng = ServeEngine(params, cfg, **kw, spec=SpecConfig(k=3, draft="skip"),
                      debug_invariants=True)
    res = eng.run([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(oracle[r.rid].tokens, res[r.rid].tokens)
    assert eng.stats()["spec_proposed"] > 0


def test_spec_disabled_is_zero_cost():
    """A spec-configured engine whose every request opts out must replay the
    spec=None engine's counters exactly — speculation is provably free when
    off — and spec stats keys appear only when spec is configured."""
    cfg, params = _model("llama3.2-1b")
    reqs = _ragged(cfg, plens=[6, 9, 4], gens=[6, 5, 7], seed=2)
    kw = dict(n_slots=2, max_len=20, kv="paged", block_size=4)
    base_eng = ServeEngine(params, cfg, **kw)
    base = base_eng.run([dataclasses.replace(r) for r in reqs])
    off_eng = ServeEngine(params, cfg, **kw,
                          spec=SpecConfig(k=3, draft="skip",
                                          default_on=False))
    off = off_eng.run([dataclasses.replace(r) for r in reqs])
    for r in reqs:
        np.testing.assert_array_equal(base[r.rid].tokens, off[r.rid].tokens)
    bs, os_ = base_eng.stats(), off_eng.stats()
    for key in ("decode_steps", "tokens", "ticks", "occupancy",
                "prefill_calls", "preemptions", "prefix_hits", "cow_copies"):
        assert bs[key] == os_[key], key
    assert os_["spec_proposed"] == os_["spec_accepted"] == 0
    assert os_["draft_steps"] == 0
    assert "spec_proposed" not in bs     # keys only when spec configured


def test_spec_requires_paged_and_no_mesh():
    cfg, params = _model("llama3.2-1b")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(params, cfg, n_slots=1, max_len=8,
                    spec=SpecConfig(k=2))
    dense_cfg = cfg.replace(sparsity=dataclasses.replace(
        cfg.sparsity, mode="srste"))
    dense_params, _ = init_model(jax.random.PRNGKey(0), dense_cfg)
    with pytest.raises(ValueError, match="compressed"):
        ServeEngine(dense_params, dense_cfg, n_slots=1, max_len=8,
                    kv="paged", spec=SpecConfig(k=2, draft="rerank"))


# ---------------------------------------------------------------- donation

def test_decode_step_donates_cache_buffers():
    """The jitted decode step takes ownership of the cache pool: after one
    step the input buffers are deleted (reused in place), not copied."""
    cfg, params = _model("llama3.2-1b")
    rng = np.random.default_rng(1)
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="paged",
                      block_size=4)
    req = synthetic_request(cfg, rng, rid=0, prompt_len=6, max_new_tokens=4)
    eng.submit(req)
    for slot, r in eng.scheduler.admit(0, fits=lambda r: True, limit=1):
        eng._admit(slot, r, 0)
    before = jax.tree_util.tree_leaves(eng.pool.caches)
    eng.step(0)
    assert all(l.is_deleted() for l in before), \
        "decode step must donate (reuse) the cache buffers, not copy them"
    assert not any(l.is_deleted()
                   for l in jax.tree_util.tree_leaves(eng.pool.caches))


def test_spec_steps_donate_cache_buffers():
    cfg, params = _model("llama3.2-1b")
    rng = np.random.default_rng(1)
    eng = ServeEngine(params, cfg, n_slots=1, max_len=16, kv="paged",
                      block_size=4, spec=SpecConfig(k=2, draft="skip"))
    req = synthetic_request(cfg, rng, rid=0, prompt_len=4, max_new_tokens=6)
    eng.submit(req)
    for slot, r in eng.scheduler.admit(0, fits=lambda r: True, limit=1):
        eng._admit(slot, r, 0)
    for t in range(4):                   # forced catch-up, then draft rounds
        before = jax.tree_util.tree_leaves(eng.pool.caches)
        eng.step(t)
        assert all(l.is_deleted() for l in before), \
            "every spec tick must donate the cache pool through its steps"
        if eng.stats()["spec_proposed"] > 0:
            break
    assert eng.stats()["spec_proposed"] > 0
