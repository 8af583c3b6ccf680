"""Differential decode-attention test net (fused paged flash decode, PR 6).

Oracle hierarchy, weakest to strongest claim:

  1. **kernel vs gather-oracle** — ``paged_gqa_decode``/``paged_mla_decode``
     (interpret mode) against the dense math run over the gathered pool,
     swept over {GQA, MLA} x {block_size 8/16} x {f32, bf16} x ragged
     ``kv_len`` (single token, len < block_size, len exactly on a block
     boundary, full span), with window/softcap variants and hot trash
     blocks (big finite garbage the mask must zero out); for GQA also with
     NaN in every block outside a slot's valid range, which the kernel
     must not read at all.
  2. **fused engine vs gather engine** — same paged ServeEngine, only the
     read path differs: tokens must be identical (matched batch composition,
     so this also holds for the row-coupled MoE/MLA family).
  3. **paged engines vs slotted dense** — the row-independent families must
     also match the PR-2 slotted layout token-for-token, closing the chain
     fused == gather == slotted.

Plus the block-table safety net: ``BlockPool.check_invariants`` cross-checks
every table against the free list (read-after-free / trash-walk detection),
property-tested under random admit/decode/retire/preempt churn and exercised
end-to-end via ``ServeEngine(debug_invariants=True)`` on a preempting trace.
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
from repro.kernels.flash_attention import (_live_block, paged_decode_traffic,
                                           paged_gqa_decode, paged_mla_decode)
from repro.models import init_model
from repro.models.common import softcap
from repro.serve import BlockPool, ServeEngine, synthetic_request
from repro.serve.paged import TRASH_BLOCK

_NEG = -1e30

_MODELS = {}


def _model(arch):
    if arch not in _MODELS:
        cfg = get_config(arch, smoke=True)
        cfg = cfg.replace(sparsity=dataclasses.replace(
            cfg.sparsity, mode="compressed", impl="xla"))
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        _MODELS[arch] = (cfg, params)
    return _MODELS[arch]


def _ragged(cfg, plens, gens, seed=9, arrival_every=0):
    rng = np.random.default_rng(seed)
    return [synthetic_request(cfg, rng, rid=i, prompt_len=p,
                              max_new_tokens=g, arrival=i * arrival_every)
            for i, (p, g) in enumerate(zip(plens, gens))]


# --------------------------------------------------- kernel-level differential

def _owned_tables(rng, b, n_blocks, table_width, lens, bs):
    """Disjoint per-slot block tables backing ``lens`` positions, trash
    elsewhere — the layout BlockPool maintains."""
    tbl = np.full((b, table_width), TRASH_BLOCK, np.int32)
    free = list(rng.permutation(np.arange(1, n_blocks)))
    for r, ln in enumerate(lens):
        for j in range(-(-int(ln) // bs)):
            tbl[r, j] = free.pop()
    return jnp.asarray(tbl)


def _gqa_pools(rng, n_blocks, bs, kvh, d, dv, dtype):
    kp = jnp.asarray(rng.standard_normal((n_blocks, bs, kvh, d)), dtype)
    vp = jnp.asarray(rng.standard_normal((n_blocks, bs, kvh, dv)), dtype)
    # hot trash: block 0 holds large finite garbage — if the kernel's
    # kv_len mask ever lets a trash tile through, the output moves by ~1e4
    kp = kp.at[TRASH_BLOCK].set(jnp.full((bs, kvh, d), 1e4, dtype))
    vp = vp.at[TRASH_BLOCK].set(jnp.full((bs, kvh, dv), 1e4, dtype))
    return kp, vp


def _gqa_gather_oracle(q, kp, vp, tbl, lens, scale, window=None, cap=None):
    """The models.attention gather read + dense score path, verbatim math."""
    b = q.shape[0]
    length = tbl.shape[1] * kp.shape[1]
    kr = kp[tbl].reshape((b, length) + kp.shape[2:])
    vr = vp[tbl].reshape((b, length) + vp.shape[2:])
    sc = jnp.einsum("bhgd,blhd->bhgl", q.astype(jnp.float32),
                    kr.astype(jnp.float32)) * scale
    sc = softcap(sc, cap)
    idx = jnp.arange(length)[None, :]
    valid = idx < lens[:, None]
    if window is not None:
        valid &= idx > lens[:, None] - 1 - window
    sc = jnp.where(valid[:, None, None, :], sc, _NEG)
    pr = jax.nn.softmax(sc, axis=-1)
    return jnp.einsum("bhgl,blhd->bhgd", pr, vr.astype(jnp.float32))


# ragged kv lengths, all the block-boundary edges for bs in {8, 16}:
# single token, len < bs, len exactly bs (boundary), bs + 1, a slot with
# table columns to spare, and one at the full table width
_LENS = (1, 7, 8, 9, 16, 31, 32, 48)


def _dead_blocks(tbl, lens, bs, window=None):
    """Pool blocks the table names outside each slot's valid block range:
    past its last valid block, and before its first under ``window``."""
    dead = []
    for r, ln in enumerate(lens):
        for j in range(tbl.shape[1]):
            live = j * bs < ln and (window is None
                                    or (j + 1) * bs > ln - window)
            if not live:
                dead.append(int(tbl[r, j]))
    return np.unique(np.asarray(dead, np.int32))


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", [
    dict(),
    dict(window=6),
    dict(cap=20.0),
    dict(window=9, cap=20.0),
    dict(poison=True),
    dict(window=6, poison=True),
    dict(cap=20.0, poison=True),
    dict(window=9, cap=20.0, poison=True),
])
def test_paged_gqa_kernel_matches_gather_oracle(bs, dtype, case):
    """With ``poison`` every block the table names outside a slot's valid
    range holds NaN: the kernel must never read it, so its output stays
    finite and identical to the same call on the clean pool."""
    b, kvh, g, d = len(_LENS), 2, 2, 32
    max_len = max(_LENS)
    tw = -(-max_len // bs)
    n_blocks = b * tw + 1
    rng = np.random.default_rng(bs)
    lens = jnp.asarray(_LENS, jnp.int32)
    tbl = _owned_tables(rng, b, n_blocks, tw, _LENS, bs)
    kp, vp = _gqa_pools(rng, n_blocks, bs, kvh, d, d, dtype)
    q = jnp.asarray(rng.standard_normal((b, kvh, g, d)), dtype)
    scale = d ** -0.5
    decode = jax.jit(lambda *a: paged_gqa_decode(
        *a, scale=scale, window=case.get("window"), cap=case.get("cap"),
        interpret=True))
    out = decode(q, kp, vp, tbl, lens)
    if case.get("poison"):
        dead = _dead_blocks(np.asarray(tbl), _LENS, bs, case.get("window"))
        assert TRASH_BLOCK in dead
        clean = out
        out = decode(q, kp.at[dead].set(jnp.nan), vp.at[dead].set(jnp.nan),
                     tbl, lens)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))
    ref = _gqa_gather_oracle(q, kp, vp, tbl, lens, scale,
                             window=case.get("window"), cap=case.get("cap"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("window", [None, 6, 9, 20])
def test_paged_gqa_fetches_only_live_columns(bs, window):
    """The K/V index map names a live column at every grid step: the step's
    own where it is live, else the nearest live one, so the pipeline's
    block index does not change across the dead steps."""
    tw = -(-max(_LENS) // bs)
    for ln in (0,) + _LENS:
        live = [j for j in range(tw) if j * bs < ln and (
            window is None or (j + 1) * bs > ln - window)]
        got = [int(_live_block(jnp.int32(j), jnp.int32(ln), bs, window))
               for j in range(tw)]
        if not live:                     # no context: column 0, never read
            assert got == [0] * tw
            continue
        assert got == [min(max(j, live[0]), live[-1]) for j in range(tw)]


@pytest.mark.parametrize("bs", [8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_mla_kernel_matches_gather_oracle(bs, dtype):
    b, h, r, rd = len(_LENS), 3, 32, 16
    max_len = max(_LENS)
    tw = -(-max_len // bs)
    n_blocks = b * tw + 1
    rng = np.random.default_rng(100 + bs)
    lens = jnp.asarray(_LENS, jnp.int32)
    tbl = _owned_tables(rng, b, n_blocks, tw, _LENS, bs)
    cp = jnp.asarray(rng.standard_normal((n_blocks, bs, r)), dtype)
    pp = jnp.asarray(rng.standard_normal((n_blocks, bs, rd)), dtype)
    cp = cp.at[TRASH_BLOCK].set(jnp.full((bs, r), 1e4, dtype))
    pp = pp.at[TRASH_BLOCK].set(jnp.full((bs, rd), 1e4, dtype))
    ql = jnp.asarray(rng.standard_normal((b, h, r)), jnp.float32)
    qp = jnp.asarray(rng.standard_normal((b, h, rd)), jnp.float32)
    scale = (r + rd) ** -0.5
    out = jax.jit(lambda *a: paged_mla_decode(
        *a, scale=scale, interpret=True))(ql, qp, cp, pp, tbl, lens)
    # gather oracle in the latent space (models.attention mla gather path)
    length = tw * bs
    cr = cp[tbl].reshape(b, length, r).astype(jnp.float32)
    pr_ = pp[tbl].reshape(b, length, rd).astype(jnp.float32)
    sc = (jnp.einsum("bhr,blr->bhl", ql, cr)
          + jnp.einsum("bhd,bld->bhl", qp, pr_)) * scale
    valid = jnp.arange(length)[None, :] < lens[:, None]
    sc = jnp.where(valid[:, None, :], sc, _NEG)
    ref = jnp.einsum("bhl,blr->bhr", jax.nn.softmax(sc, axis=-1), cr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_paged_traffic_model_fused_below_gather():
    t = paged_decode_traffic(4, 8, 16, [1, 17, 64, 128], 256, 256)
    assert t["fused_bytes"] < t["gather_bytes"]
    # fused reads scale with occupancy, gather with the full table span
    t_idle = paged_decode_traffic(4, 8, 16, [1, 1, 1, 1], 256, 256)
    assert t_idle["fused_bytes"] < t["fused_bytes"]
    assert t_idle["gather_bytes"] == t["gather_bytes"]


# ------------------------------------------ engine-level: fused == gather ==
# slotted (tokens), per family

def _three_way(arch, block_size=4, plens=(6, 11, 4), gens=(4, 2, 5),
               max_len=16, slotted_too=True):
    cfg, params = _model(arch)
    reqs = _ragged(cfg, plens=list(plens), gens=list(gens))
    gather = ServeEngine(params, cfg, n_slots=2, max_len=max_len, kv="paged",
                         block_size=block_size).run(reqs)
    fused = ServeEngine(params, cfg, n_slots=2, max_len=max_len, kv="paged",
                        block_size=block_size, attn="fused",
                        debug_invariants=True).run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(
            gather[r.rid].tokens, fused[r.rid].tokens,
            err_msg=f"{arch} rid={r.rid}: fused != gather")
    if slotted_too:
        slotted = ServeEngine(params, cfg, n_slots=2,
                              max_len=max_len).run(reqs)
        for r in reqs:
            np.testing.assert_array_equal(
                slotted[r.rid].tokens, fused[r.rid].tokens,
                err_msg=f"{arch} rid={r.rid}: fused != slotted dense")


@pytest.mark.parametrize("block_size", [8, 16])
def test_fused_gqa_serves_identically(block_size):
    """Dense GQA: fused == gather == slotted, at block 8 and at block 16
    (table width 1 — the whole request in one block)."""
    _three_way("llama3.2-1b", block_size=block_size)


def test_fused_windowed_softcap_serves_identically():
    """gemma2: local (windowed) / global pairs + attention softcap through
    the fused kernel's window/cap masks."""
    _three_way("gemma2-9b", block_size=4)


def test_fused_audio_self_attention_serves_identically():
    """whisper: paged decoder self K/V fused, slot-indexed cross K/V
    untouched (bucket-UP pad prefill path)."""
    _three_way("whisper-small", block_size=4)


def test_fused_mla_serves_identically_to_gather():
    """MLA (deepseek-v2-lite, MoE family): expert capacity couples batch
    rows, so the slotted comparison needs matched composition — but fused vs
    gather share the engine schedule exactly, and must agree token-for-token
    through the absorbed latent kernel."""
    _three_way("deepseek-v2-lite-16b", block_size=4, slotted_too=False)


def test_fused_single_token_requests():
    """max_new_tokens=1 (prefill-only) plus a 1-token prompt: the kernel's
    kv_len=1 edge through the engine."""
    cfg, params = _model("llama3.2-1b")
    reqs = _ragged(cfg, plens=[1, 5], gens=[3, 1], seed=3)
    gather = ServeEngine(params, cfg, n_slots=2, max_len=8, kv="paged",
                         block_size=4).run(reqs)
    fused = ServeEngine(params, cfg, n_slots=2, max_len=8, kv="paged",
                        block_size=4, attn="fused").run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(gather[r.rid].tokens,
                                      fused[r.rid].tokens)


def test_fused_requires_paged_layout():
    cfg, params = _model("llama3.2-1b")
    with pytest.raises(ValueError, match="fused"):
        ServeEngine(params, cfg, n_slots=1, max_len=8, attn="fused")
    with pytest.raises(ValueError, match="attn"):
        ServeEngine(params, cfg, n_slots=1, max_len=8, kv="paged",
                    attn="flash3")


# ----------------------------------------------------- block-table safety net

def _pool(n_slots=3, max_len=16, block_size=4, n_blocks=None):
    cfg, _ = _model("llama3.2-1b")
    return BlockPool(cfg, n_slots, max_len, block_size, n_blocks)


def test_check_invariants_detects_read_after_free():
    """A table naming a freed block is exactly the stale read the fused
    kernel must never perform — the cross-check has to catch it."""
    p = _pool(n_slots=2, max_len=8, block_size=4)
    assert p.alloc(0, 2) and p.alloc(1, 1)
    freed = p._owned[1][0]
    p.free(1)
    p.table[0, 1] = freed                   # corrupt: point at a freed block
    p._owned[0][1] = freed
    with pytest.raises(AssertionError, match="freed block"):
        p.check_invariants()


def test_check_invariants_detects_unbacked_decode_position():
    p = _pool(n_slots=1, max_len=16, block_size=4)
    assert p.alloc(0, 1)                    # backs positions [0, 4)
    p.check_invariants(active_pos={0: 3})   # fine: inside the owned block
    with pytest.raises(AssertionError, match="walk into trash"):
        p.check_invariants(active_pos={0: 4})


@settings(max_examples=20, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 2),
                          st.integers(0, 15)), max_size=50))
def test_tables_never_expose_freed_blocks_under_churn(ops):
    """Random admit/decode/retire/preempt sequences: at every step, every
    active slot's read window [0, pos] resolves through owned, non-free,
    non-trash blocks — no interleaving hands the fused kernel a freed or
    trash block."""
    p = _pool(n_slots=3, max_len=16, block_size=4, n_blocks=8)
    pos = {}                                # slot -> current decode position
    for kind, slot, arg in ops:
        if kind == 0 and slot not in pos:   # admit: seed arg+1 positions
            n_seed = arg % p.max_len + 1
            if p.alloc(slot, p.blocks_for(n_seed)):
                pos[slot] = n_seed - 1
        elif kind == 1 and slot in pos:     # decode tick: grow lazily
            if pos[slot] + 1 < p.max_len and p.ensure(slot, pos[slot] + 1):
                pos[slot] += 1
        elif kind == 2 and slot in pos:     # retire
            p.free(slot)
            del pos[slot]
        elif kind == 3 and pos:             # preempt the newest active slot
            victim = max(pos)
            p.free(victim)
            del pos[victim]
        p.check_invariants(active_pos=pos)


def test_engine_debug_invariants_through_preemption():
    """Oversubscribed fused trace with the per-tick cross-check armed:
    preemptions fire, invariants hold every tick, tokens still match the
    gather oracle."""
    cfg, params = _model("llama3.2-1b")
    reqs = _ragged(cfg, plens=[4, 4, 4], gens=[6, 6, 6], seed=5)
    gather = ServeEngine(params, cfg, n_slots=3, max_len=12, kv="paged",
                         block_size=2, n_blocks=11).run(reqs)
    eng = ServeEngine(params, cfg, n_slots=3, max_len=12, kv="paged",
                      block_size=2, n_blocks=11, attn="fused",
                      debug_invariants=True)
    fused = eng.run(reqs)
    assert eng.preemptions > 0
    for r in reqs:
        np.testing.assert_array_equal(gather[r.rid].tokens,
                                      fused[r.rid].tokens)
    eng.pool.check_invariants(active_pos={})
