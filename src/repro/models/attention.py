"""Attention: GQA (+ local/global, softcap), MLA, cross-attention, KV caches.

Prefill/training uses a chunked online-softmax attention (pure JAX
flash-attention formulation): memory is O(q_chunk * kv_chunk) per step instead
of O(S^2), which is what lets the 32k-prefill shapes compile with sane
footprints.  Decode is a single-token step against a preallocated cache.

All projections are SparseLinear — the paper's N:M technique applied to the
attention GEMMs.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparse_matmul import dense_forward_view, _decompress_xla
from repro.dist.api import (constrain, current_mesh, current_rules,
                            logical_to_pspec)
from repro.kernels.flash_attention import (paged_gqa_decode, paged_gqa_verify,
                                           paged_mla_decode, paged_mla_verify)
from repro.kernels.ops import pallas_interpret
from repro.models.common import (Params, apply_rope, rope_angles, softcap,
                                 sp_linear_apply, sp_linear_init)
from repro.models.config import ArchConfig

_NEG = -1e30


def _fused_kernel(kernel, args, head_axes, n_heads: int, **kw):
    """Call a fused paged-attention kernel, natively on a TPU and interpreted
    elsewhere.  GSPMD cannot partition a Pallas call, so under a
    multi-device mesh the call is a ``shard_map`` over the heads: operand i
    splits on axis ``head_axes[i]`` (None: replicated, like the block table),
    the output on the query's head axis.  Heads that do not divide the mesh
    replicate, as the pool's own head axis does then."""
    fn = functools.partial(kernel, interpret=pallas_interpret(), **kw)
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return fn(*args)
    from jax.sharding import PartitionSpec as P
    heads = logical_to_pspec(("act_heads",), current_rules(), mesh=mesh,
                             shape=(n_heads,))
    name = heads[0] if len(heads) else None

    def spec(axis):
        if axis is None or name is None:
            return P()
        return P(*([None] * axis + [name]))
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(a) for a in head_axes),
                         out_specs=spec(head_axes[0]), check_vma=False)(*args)


def _pick_chunk(s: int, want: int) -> int:
    c = min(want, s)
    while s % c:
        c -= 1
    return c


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: Optional[int] = None,
                      cap: Optional[float] = None, scale: Optional[float] = None,
                      q_chunk: int = 512, kv_chunk: int = 1024,
                      chain_bf16: bool = False) -> jax.Array:
    """Online-softmax attention.

    q [B, Sq, H, Dq], k [B, Sk, KVH, Dq], v [B, Sk, KVH, Dv]; H % KVH == 0.
    Returns [B, Sq, H, Dv].  Assumes q tokens occupy positions
    Sk - Sq … Sk - 1 (training: Sq == Sk).
    """
    b, sq, h, dq = q.shape
    _, sk, kvh, _ = k.shape
    dv = v.shape[-1]
    g = h // kvh
    scale = scale if scale is not None else dq ** -0.5
    cq = _pick_chunk(sq, q_chunk)
    ck = _pick_chunk(sk, kv_chunk)
    nq, nk = sq // cq, sk // ck
    q_off = sk - sq

    qg = q.reshape(b, nq, cq, kvh, g, dq).transpose(1, 0, 3, 4, 2, 5)
    kc = k.reshape(b, nk, ck, kvh, dq).transpose(1, 0, 3, 2, 4)
    vc = v.reshape(b, nk, ck, kvh, dv).transpose(1, 0, 3, 2, 4)

    def q_step(_, qi_qc):
        qi, qcnk = qi_qc                     # qcnk [b, kvh, g, cq, dq]
        qpos = q_off + qi * cq + jnp.arange(cq)

        def kv_step(carry, ki_kv):
            m, l, acc = carry
            ki, kck, vck = ki_kv             # kck [b, kvh, ck, dq]
            kpos = ki * ck + jnp.arange(ck)
            # chain_bf16 (§Perf): the [cq, ck] tensors are the dominant HBM
            # stream of the unfused attention — keep them bf16 (m/l stats and
            # accumulations stay f32; exp(s - m) is scale-normalized so bf16
            # resolution is adequate).
            cdt = jnp.bfloat16 if chain_bf16 else jnp.float32
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qcnk.astype(jnp.float32),
                           kck.astype(jnp.float32),
                           preferred_element_type=jnp.float32) * scale
            s = softcap(s, cap)
            mask = jnp.ones((cq, ck), bool)
            if causal:
                mask &= qpos[:, None] >= kpos[None, :]
            if window is not None:
                mask &= qpos[:, None] - kpos[None, :] < window
            s = jnp.where(mask, s, _NEG).astype(cdt)
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.where(mask, jnp.exp(s.astype(jnp.float32)
                                        - m_new[..., None]), 0.0).astype(cdt)
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.astype(jnp.float32).sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(jnp.float32),
                vck.astype(jnp.float32),
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kvh, g, cq), _NEG, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, cq), jnp.float32)
        a0 = jnp.zeros((b, kvh, g, cq, dv), jnp.float32)
        # remat the kv step: without it, autodiff saves the [cq, ck]
        # probability tile of EVERY (qi, ki) pair — S^2 residuals, the exact
        # blow-up flash attention's backward recompute exists to avoid.
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), (m0, l0, a0), (jnp.arange(nk), kc, vc))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out                      # [b, kvh, g, cq, dv]

    _, outs = jax.lax.scan(jax.checkpoint(q_step), None, (jnp.arange(nq), qg))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out.astype(v.dtype)


# ------------------------------------------------------------------------ GQA

def gqa_init(key, cfg: ArchConfig, dtype):
    d, hd, h, kv = cfg.d_model, cfg.hd(), cfg.n_heads, cfg.n_kv
    ks = jax.random.split(key, 4)
    sp = cfg.sparsity
    p, s = {}, {}
    p["wq"], s["wq"] = sp_linear_init(ks[0], d, h * hd, sp, dtype,
                                      ("tp", "fsdp"), cfg.qkv_bias)
    p["wk"], s["wk"] = sp_linear_init(ks[1], d, kv * hd, sp, dtype,
                                      ("tp", "fsdp"), cfg.qkv_bias)
    p["wv"], s["wv"] = sp_linear_init(ks[2], d, kv * hd, sp, dtype,
                                      ("tp", "fsdp"), cfg.qkv_bias)
    p["wo"], s["wo"] = sp_linear_init(ks[3], h * hd, d, sp, dtype,
                                      ("fsdp", "tp"))
    return p, s


def gqa_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype,
                   window: Optional[int] = None):
    """KV cache.  Windowed layers get a ring buffer of length window —
    at 500k context a 4k-window cache is 128x smaller (see DESIGN.md)."""
    length = min(max_len, window) if window else max_len
    kv, hd = cfg.n_kv, cfg.hd()
    z = jnp.zeros((batch, length, kv, hd), dtype)
    # seq over model = context-parallel decode: always divisible (32k/16),
    # and the only way a 1.5TB 88-layer 32k cache fits per device when the
    # kv-head count (8) doesn't divide the tp axis.
    spec = ("act_batch", "act_seq_sp", "act_heads", None)
    return ({"k": z, "v": z}, {"k": spec, "v": spec})


def _paged_write(cache, updates, block_table, cache_pos):
    """Write one new token per batch row through the block table.

    Cache leaves are block pools ``[n_blocks, block_size, ...]``; row r's
    token at position p lives at physical block
    ``block_table[r, p // block_size]``, offset ``p % block_size`` — the
    software analog of the paper's indexed register reads (``cache_pos``
    must be the int32 [B] per-slot vector).  ``updates`` maps leaf name to
    that row's new value ([B, ...], no seq axis)."""
    bsz = next(iter(cache.values())).shape[1]
    posv = jnp.reshape(cache_pos, (-1,))
    blk = block_table[jnp.arange(posv.shape[0]), posv // bsz]
    off = posv % bsz
    return {name: cache[name].at[blk, off].set(val.astype(cache[name].dtype))
            for name, val in updates.items()}


def _paged_update(cache, updates, block_table, cache_pos):
    """``_paged_write`` + gather each row's stream back in logical order:
    returns ``(new_cache, reads, length)`` with ``reads[name]`` in the plain
    position-indexed layout ``[B, table_width * block_size, ...]`` the
    non-paged score path expects.  This is the gather read path — the
    interpret-mode oracle the fused kernels are tested against; it pays the
    indirection AND a dense materialization of the whole table span."""
    bsz = next(iter(cache.values())).shape[1]
    b = jnp.reshape(cache_pos, (-1,)).shape[0]
    length = block_table.shape[1] * bsz
    new = _paged_write(cache, updates, block_table, cache_pos)
    reads = {name: c[block_table].reshape((b, length) + c.shape[2:])
             for name, c in new.items()}
    return new, reads, length


def _paged_write_span(cache, updates, block_table, cache_pos):
    """Write a span of S consecutive tokens per batch row through the block
    table (the speculative verify path: all k+1 positions land in one call).

    ``updates`` maps leaf name to ``[B, S, ...]``; row r's token at offset i
    goes to logical position ``cache_pos[r] + i``, resolved through the same
    table indirection as ``_paged_write``.  The engine must have backed and
    COW'd every block the span touches before the call (write-exclusivity is
    per-span here, checked by ``check_invariants(active_pos=...)``)."""
    bsz = next(iter(cache.values())).shape[1]
    posv = jnp.reshape(cache_pos, (-1,))
    span = next(iter(updates.values())).shape[1]
    posm = posv[:, None] + jnp.arange(span)              # [B, S]
    bidx = jnp.arange(posv.shape[0])[:, None]
    blk = block_table[bidx, posm // bsz]
    off = posm % bsz
    return {name: cache[name].at[blk, off].set(val.astype(cache[name].dtype))
            for name, val in updates.items()}


def _paged_update_span(cache, updates, block_table, cache_pos):
    """``_paged_write_span`` + gather, the span analog of ``_paged_update``."""
    bsz = next(iter(cache.values())).shape[1]
    b = jnp.reshape(cache_pos, (-1,)).shape[0]
    length = block_table.shape[1] * bsz
    new = _paged_write_span(cache, updates, block_table, cache_pos)
    reads = {name: c[block_table].reshape((b, length) + c.shape[2:])
             for name, c in new.items()}
    return new, reads, length


def _paged_kv_len(cache_pos) -> jax.Array:
    """Valid positions per slot, the just-written token included."""
    return jnp.reshape(cache_pos, (-1,)).astype(jnp.int32) + 1


def gqa_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              positions: jax.Array, window: Optional[int] = None,
              cache: Optional[Params] = None,
              cache_pos: Optional[jax.Array] = None,
              block_table: Optional[jax.Array] = None,
              return_kv: bool = False):
    """x [B, S, d].  Training/prefill when cache is None (or return_kv),
    single-token decode when cache is given (x [B, 1, d]).  cache_pos is a
    scalar (whole batch at one position) or an int32 [B] vector of per-slot
    positions (continuous batching: every batch row is an independent request
    at its own depth).

    With ``block_table`` (int32 [B, max_blocks]) the cache leaves are a paged
    block pool [n_blocks, block_size, kv, hd]: row r's token at position p
    lives at physical block ``block_table[r, p // block_size]``, offset
    ``p % block_size`` — the block-table indirection of ``serve.paged``
    (cache_pos must be the [B] per-slot vector in this mode).  How the pool
    is *read* is ``cfg.attn_impl``: 'gather' materializes each row's stream
    into a dense layout first (the oracle), 'fused' walks the table inside
    ``kernels.flash_attention.paged_gqa_decode``."""
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd()
    sp = cfg.sparsity
    if jnp.ndim(positions) == 1:
        # per-slot decode: [B] -> [B, S] consecutive positions (S == 1 for
        # the plain decode step; S == k+1 for the speculative verify span)
        positions = positions[:, None] + jnp.arange(s)

    q = sp_linear_apply(p["wq"], x, sp).reshape(b, s, h, hd)
    k = sp_linear_apply(p["wk"], x, sp).reshape(b, s, kv, hd)
    v = sp_linear_apply(p["wv"], x, sp).reshape(b, s, kv, hd)
    q = constrain(q, "act_batch", "act_seq", "act_heads", None)

    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is None:
        # expand KV heads so the head axis shards evenly under TP (the
        # broadcast fuses into the attention einsum; HBM caches stay grouped)
        g = h // kv
        ke = constrain(jnp.repeat(k, g, axis=2),
                       "act_batch", "act_seq", "act_heads", None)
        ve = constrain(jnp.repeat(v, g, axis=2),
                       "act_batch", "act_seq", "act_heads", None)
        o = chunked_attention(q, ke, ve, causal=True, window=window,
                              cap=cfg.softcap_attn,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              chain_bf16=cfg.attn_chain_bf16)
        new_kv = {"k": k, "v": v} if return_kv else None
    elif block_table is not None and cfg.attn_impl == "fused":
        if s == 1:
            # fused paged decode: write through the table, then let the
            # Pallas flash-decoding kernel walk the table itself — the pool
            # is never materialized into a dense position-indexed copy (the
            # bandwidth win the gather path below throws away)
            new_kv = _paged_write(cache, {"k": k[:, 0], "v": v[:, 0]},
                                  block_table, cache_pos)
            o = _fused_kernel(
                paged_gqa_decode,
                (q.reshape(b, kv, h // kv, hd), new_kv["k"], new_kv["v"],
                 block_table, _paged_kv_len(cache_pos)),
                (1, 2, 2, None, None), kv, scale=hd ** -0.5, window=window,
                cap=cfg.softcap_attn)
            o = o.reshape(b, 1, h, hd).astype(x.dtype)
        else:
            # fused paged verify span: write all S positions, then score
            # query offset i against kv_len + i positions (causal inside the
            # span) via one single-query kernel launch per offset
            new_kv = _paged_write_span(cache, {"k": k, "v": v},
                                       block_table, cache_pos)
            o = _fused_kernel(
                paged_gqa_verify,
                (q.reshape(b, s, kv, h // kv, hd), new_kv["k"], new_kv["v"],
                 block_table, _paged_kv_len(cache_pos)),
                (2, 2, 2, None, None), kv, scale=hd ** -0.5, window=window,
                cap=cfg.softcap_attn)
            o = o.reshape(b, s, h, hd).astype(x.dtype)
    elif block_table is not None and s > 1:
        # paged verify span, gather read: write the span, gather the table
        # back to the plain layout, score every offset with its own causal
        # window — per query the same masked-softmax chain as the s == 1
        # gather path below (in the paged regime idx <= pos is exactly the
        # ring formula's validity test), so verify logits at an already-
        # committed position match the plain decode step's
        new_kv, reads, length = _paged_update_span(
            cache, {"k": k, "v": v}, block_table, cache_pos)
        k_read, v_read = reads["k"], reads["v"]
        g = h // kv
        qg = q.reshape(b, s, kv, g, hd)
        sc = jnp.einsum("bshgd,blhd->bshgl", qg.astype(jnp.float32),
                        k_read.astype(jnp.float32)) * hd ** -0.5
        sc = softcap(sc, cfg.softcap_attn)
        idx = jnp.arange(length)[None, None, :]
        posq = jnp.reshape(cache_pos, (-1, 1)) + jnp.arange(s)[None, :]
        valid = idx <= posq[:, :, None]
        if window is not None:
            valid &= idx > (posq[:, :, None] - window)
        sc = jnp.where(valid[:, :, None, None, :], sc, _NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bshgl,blhd->bshgd", pr, v_read.astype(jnp.float32))
        o = o.reshape(b, s, h, hd).astype(x.dtype)
    else:
        if block_table is not None:
            # paged decode, gather read: write through the table, read the
            # pool back via gather so the score einsum sees the same plain
            # [B, T*bs, kv, hd] layout the slotted path uses (_paged_update)
            new_kv, reads, length = _paged_update(
                cache, {"k": k[:, 0], "v": v[:, 0]}, block_table, cache_pos)
            k_read, v_read = reads["k"], reads["v"]
        else:
            # decode: ring-buffer insertion.  Slot j of a length-L cache holds
            # absolute position p = pos - ((pos - j) mod L); p < 0 marks an
            # unfilled slot.  For L == max_len this reduces to the plain
            # append-at-pos cache, so one code path serves both.
            length = cache["k"].shape[1]
            slot = cache_pos % length
            if jnp.ndim(cache_pos):
                # per-slot positions: row r writes at its own (slot[r]) offset
                bidx = jnp.arange(b)
                ck = cache["k"].at[bidx, slot].set(
                    k[:, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[bidx, slot].set(
                    v[:, 0].astype(cache["v"].dtype))
            else:
                ck = jax.lax.dynamic_update_slice(cache["k"],
                                                  k.astype(cache["k"].dtype),
                                                  (0, slot, 0, 0))
                cv = jax.lax.dynamic_update_slice(cache["v"],
                                                  v.astype(cache["v"].dtype),
                                                  (0, slot, 0, 0))
            new_kv = {"k": ck, "v": cv}
            k_read, v_read = ck, cv
        g = h // kv
        qg = q.reshape(b, kv, g, hd)
        sc = jnp.einsum("bhgd,blhd->bhgl", qg.astype(jnp.float32),
                        k_read.astype(jnp.float32)) * hd ** -0.5
        sc = softcap(sc, cfg.softcap_attn)
        idx = jnp.arange(length)[None, :]
        posb = jnp.reshape(cache_pos, (-1, 1))          # [B, 1] or [1, 1]
        abs_pos = posb - jnp.mod(posb - idx, length)
        valid = abs_pos >= 0
        if window is not None:
            valid &= abs_pos > posb - window
        sc = jnp.where(valid[:, None, None, :], sc, _NEG)
        pr = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhgl,blhd->bhgd", pr, v_read.astype(jnp.float32))
        o = o.reshape(b, 1, h, hd).astype(x.dtype)

    y = sp_linear_apply(p["wo"], o.reshape(b, s, h * hd), sp)
    return constrain(y, "act_batch", "act_seq", None), new_kv


# ------------------------------------------------------------------------ MLA

def mla_init(key, cfg: ArchConfig, dtype):
    d, h = cfg.d_model, cfg.n_heads
    qk = cfg.qk_nope_dim + cfg.qk_rope_dim
    ks = jax.random.split(key, 5)
    sp = cfg.sparsity
    p, s = {}, {}
    p["wq"], s["wq"] = sp_linear_init(ks[0], d, h * qk, sp, dtype, ("tp", "fsdp"))
    p["wdkv"], s["wdkv"] = sp_linear_init(
        ks[1], d, cfg.kv_lora + cfg.qk_rope_dim, sp, dtype, (None, "fsdp"))
    p["wuk"], s["wuk"] = sp_linear_init(
        ks[2], cfg.kv_lora, h * cfg.qk_nope_dim, sp, dtype, ("tp", "fsdp"))
    p["wuv"], s["wuv"] = sp_linear_init(
        ks[3], cfg.kv_lora, h * cfg.v_head_dim, sp, dtype, ("tp", "fsdp"))
    p["wo"], s["wo"] = sp_linear_init(
        ks[4], h * cfg.v_head_dim, d, sp, dtype, ("fsdp", "tp"))
    return p, s


def mla_cache_init(cfg: ArchConfig, batch: int, max_len: int, dtype):
    ckv = jnp.zeros((batch, max_len, cfg.kv_lora), dtype)
    kpe = jnp.zeros((batch, max_len, cfg.qk_rope_dim), dtype)
    return ({"ckv": ckv, "kpe": kpe},
            {"ckv": ("act_batch", "act_seq_sp", None),
             "kpe": ("act_batch", "act_seq_sp", None)})


def _mla_qkv(p, x, cfg, positions):
    b, s, _ = x.shape
    h = cfg.n_heads
    nd, rd = cfg.qk_nope_dim, cfg.qk_rope_dim
    sp = cfg.sparsity
    q = sp_linear_apply(p["wq"], x, sp).reshape(b, s, h, nd + rd)
    qn, qpe = q[..., :nd], q[..., nd:]
    dkv = sp_linear_apply(p["wdkv"], x, sp)
    ckv, kpe = dkv[..., :cfg.kv_lora], dkv[..., cfg.kv_lora:]
    cos, sin = rope_angles(positions, rd, cfg.rope_theta)
    qpe = apply_rope(qpe, cos, sin)
    kpe = apply_rope(kpe[..., None, :], cos, sin)[..., 0, :]   # single kv head
    return qn, qpe, ckv, kpe


def mla_apply(p: Params, x: jax.Array, cfg: ArchConfig, *,
              positions: jax.Array, cache: Optional[Params] = None,
              cache_pos: Optional[jax.Array] = None,
              block_table: Optional[jax.Array] = None,
              return_kv: bool = False):
    b, s, d = x.shape
    h, nd, rd, vd = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    sp = cfg.sparsity
    if jnp.ndim(positions) == 1:
        # per-slot decode: [B] -> [B, S] consecutive positions (S == 1 for
        # the plain decode step; S == k+1 for the speculative verify span)
        positions = positions[:, None] + jnp.arange(s)
    qn, qpe, ckv, kpe = _mla_qkv(p, x, cfg, positions)
    scale = (nd + rd) ** -0.5

    if cache is None:
        # up-project and run standard chunked attention (prefill/train)
        kn = sp_linear_apply(p["wuk"], ckv, sp).reshape(b, s, h, nd)
        vv = sp_linear_apply(p["wuv"], ckv, sp).reshape(b, s, h, vd)
        q = jnp.concatenate([qn, qpe], axis=-1)
        k = jnp.concatenate([kn, jnp.broadcast_to(kpe[:, :, None, :],
                                                  (b, s, h, rd))], axis=-1)
        o = chunked_attention(q, k, vv, causal=True, scale=scale,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              chain_bf16=cfg.attn_chain_bf16)
        new_kv = {"ckv": ckv, "kpe": kpe} if return_kv else None
    else:
        # absorbed decode: scores/outputs computed in the latent space —
        # the cache stays [kv_lora + rope] per token (MLA's memory win).
        # cache_pos: scalar, or [B] per-slot positions (continuous batching).
        fused = block_table is not None and cfg.attn_impl == "fused"
        if fused:
            # fused paged absorbed decode: write through the table, walk it
            # inside the kernel — scores, softmax, and the latent context
            # never leave VMEM (see paged_mla_decode)
            if s == 1:
                new_kv = _paged_write(cache,
                                      {"ckv": ckv[:, 0], "kpe": kpe[:, 0]},
                                      block_table, cache_pos)
            else:
                new_kv = _paged_write_span(cache, {"ckv": ckv, "kpe": kpe},
                                           block_table, cache_pos)
            cc_read = cp_read = None
        elif block_table is not None:
            # paged absorbed decode, gather read: latent cache leaves are
            # block pools [n_blocks, bs, r]; same indirection as GQA
            # (see _paged_update; the span variant is the verify path)
            if s == 1:
                new_kv, reads, _ = _paged_update(
                    cache, {"ckv": ckv[:, 0], "kpe": kpe[:, 0]}, block_table,
                    cache_pos)
            else:
                new_kv, reads, _ = _paged_update_span(
                    cache, {"ckv": ckv, "kpe": kpe}, block_table, cache_pos)
            cc_read, cp_read = reads["ckv"], reads["kpe"]
        elif jnp.ndim(cache_pos):
            bidx = jnp.arange(b)
            cc = cache["ckv"].at[bidx, cache_pos].set(
                ckv[:, 0].astype(cache["ckv"].dtype))
            cp = cache["kpe"].at[bidx, cache_pos].set(
                kpe[:, 0].astype(cache["kpe"].dtype))
            new_kv = {"ckv": cc, "kpe": cp}
            cc_read, cp_read = cc, cp
        else:
            cc = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), (0, cache_pos, 0))
            cp = jax.lax.dynamic_update_slice(
                cache["kpe"], kpe.astype(cache["kpe"].dtype), (0, cache_pos, 0))
            new_kv = {"ckv": cc, "kpe": cp}
            cc_read, cp_read = cc, cp
        # materialize per-head up-proj weights (dense view for the einsum)
        wuk_dense = _dense_weight(p["wuk"], cfg)        # [h*nd, kv_lora]
        wuv_dense = _dense_weight(p["wuv"], cfg)        # [h*vd, kv_lora]
        wuk3 = wuk_dense.reshape(h, nd, cfg.kv_lora)
        wuv3 = wuv_dense.reshape(h, vd, cfg.kv_lora)
        if s == 1:
            qlat = jnp.einsum("bhd,hdr->bhr", qn[:, 0].astype(jnp.float32),
                              wuk3.astype(jnp.float32))
            if fused:
                ov = _fused_kernel(
                    paged_mla_decode,
                    (qlat, qpe[:, 0].astype(jnp.float32), new_kv["ckv"],
                     new_kv["kpe"], block_table, _paged_kv_len(cache_pos)),
                    (1, 1, None, None, None, None), h, scale=scale)
            else:
                sc = jnp.einsum("bhr,blr->bhl", qlat,
                                cc_read.astype(jnp.float32))
                sc += jnp.einsum("bhd,bld->bhl", qpe[:, 0].astype(jnp.float32),
                                 cp_read.astype(jnp.float32))
                sc *= scale
                idx = jnp.arange(cc_read.shape[1])[None, :]
                posb = jnp.reshape(cache_pos, (-1, 1))  # [B, 1] or [1, 1]
                sc = jnp.where((idx <= posb)[:, None, :], sc, _NEG)
                pr = jax.nn.softmax(sc, axis=-1)
                ov = jnp.einsum("bhl,blr->bhr", pr,
                                cc_read.astype(jnp.float32))
            o = jnp.einsum("bhr,hdr->bhd", ov, wuv3.astype(jnp.float32))
            o = o.reshape(b, 1, h, vd).astype(x.dtype)
        else:
            # verify span: query offset i masks to idx <= cache_pos + i —
            # per query the same absorbed-score chain as the s == 1 path
            qlat = jnp.einsum("bshd,hdr->bshr", qn.astype(jnp.float32),
                              wuk3.astype(jnp.float32))
            if fused:
                ov = _fused_kernel(
                    paged_mla_verify,
                    (qlat, qpe.astype(jnp.float32), new_kv["ckv"],
                     new_kv["kpe"], block_table, _paged_kv_len(cache_pos)),
                    (2, 2, None, None, None, None), h, scale=scale)
            else:
                sc = jnp.einsum("bshr,blr->bshl", qlat,
                                cc_read.astype(jnp.float32))
                sc += jnp.einsum("bshd,bld->bshl", qpe.astype(jnp.float32),
                                 cp_read.astype(jnp.float32))
                sc *= scale
                idx = jnp.arange(cc_read.shape[1])[None, None, :]
                posq = (jnp.reshape(cache_pos, (-1, 1))
                        + jnp.arange(s)[None, :])       # [B, S]
                sc = jnp.where((idx <= posq[:, :, None])[:, :, None, :],
                               sc, _NEG)
                pr = jax.nn.softmax(sc, axis=-1)
                ov = jnp.einsum("bshl,blr->bshr", pr,
                                cc_read.astype(jnp.float32))
            o = jnp.einsum("bshr,hdr->bshd", ov, wuv3.astype(jnp.float32))
            o = o.astype(x.dtype)

    y = sp_linear_apply(p["wo"], o.reshape(b, s, h * vd), sp)
    return constrain(y, "act_batch", "act_seq", None), new_kv


def _dense_weight(lin_params: Params, cfg: ArchConfig) -> jax.Array:
    """Dense view of a (possibly compressed/masked/srste) linear weight,
    consistent with what sp_linear_apply multiplies by (shared forward
    semantics: sparse_matmul.dense_forward_view)."""
    spc = cfg.sparsity
    if "w_vals" in lin_params:
        o, nnz = lin_params["w_vals"].shape
        k = nnz * spc.m // spc.n
        return _decompress_xla(lin_params["w_vals"], lin_params["w_idx"],
                               spc.n, spc.m, k)
    return dense_forward_view(lin_params, spc)


# -------------------------------------------------------------- cross-attention

def cross_attn_init(key, cfg: ArchConfig, dtype):
    return gqa_init(key, cfg, dtype)


def cross_attn_apply(p: Params, x: jax.Array, enc_kv: Tuple[jax.Array, jax.Array],
                     cfg: ArchConfig) -> jax.Array:
    """Decoder cross-attention over precomputed encoder K/V [B, Se, KV, hd]."""
    b, s, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd()
    sp = cfg.sparsity
    q = sp_linear_apply(p["wq"], x, sp).reshape(b, s, h, hd)
    k, v = enc_kv
    o = chunked_attention(q, k, v, causal=False, q_chunk=cfg.q_chunk,
                          kv_chunk=cfg.kv_chunk,
                          chain_bf16=cfg.attn_chain_bf16)
    y = sp_linear_apply(p["wo"], o.reshape(b, s, h * hd), sp)
    return constrain(y, "act_batch", "act_seq", None)


def cross_kv(p: Params, enc_out: jax.Array, cfg: ArchConfig):
    """Precompute cross-attention K/V from encoder output (once per request)."""
    b, se, _ = enc_out.shape
    kv, hd = cfg.n_kv, cfg.hd()
    sp = cfg.sparsity
    k = sp_linear_apply(p["wk"], enc_out, sp).reshape(b, se, kv, hd)
    v = sp_linear_apply(p["wv"], enc_out, sp).reshape(b, se, kv, hd)
    return k, v
