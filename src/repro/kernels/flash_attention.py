"""Pallas TPU kernel: flash attention (causal / windowed / softcapped).

Beyond-paper optimization (EXPERIMENTS.md §Perf): the dry-run roofline shows
the pure-JAX chunked attention dominated by HBM traffic of the [q_chunk,
kv_chunk] score/probability tensors at every fusion boundary — the classic
gap a fused attention kernel closes by keeping the whole online-softmax
update in VMEM.  Same vindexmac philosophy as nm_spmm: bound the working set,
pin it in fast memory, never let the intermediate touch HBM.

Layout: q/k/v [BH, S, D] (batch*heads flattened; GQA is expanded by the ops
wrapper).  Grid (BH, q_blocks, kv_blocks); kv is the innermost (sequential)
axis with m/l/acc scratch carried across kv steps.  Causal masking skips
nothing structurally (blocks above the diagonal still run, fully masked) —
block-skipping is a further optimization left measured in §Perf.

Causal-mask anchor (``q_off``): query row i of a [BH, Sq, D] call is masked
at absolute position ``q_off + i``, and key column j at absolute position
``j`` — so ``q_off`` is where the query window starts inside the key
sequence.  The default ``q_off = Sk - Sq`` places the queries at the
*suffix* of the keys, which covers both training (Sq == Sk, q_off == 0) and
the serve stack's bucketed prefill: a prompt bucketed DOWN to ``pb`` tokens
prefills positions [0, pb) with q_off == 0, and the forced-decode replay of
the remaining ``Sq = plen - pb`` tokens attends over all ``Sk = plen``
positions with q_off == pb — nonzero, and exactly Sk - Sq.  Pass ``q_off=``
explicitly only to break that suffix assumption (it shifts every query's
causal/window anchor; keys are always at positions [0, Sk)).

``paged_gqa_decode`` / ``paged_mla_decode`` are the decode-side siblings:
flash-decoding over a *paged* KV pool, resolving the per-slot block table
inside the kernel (scalar-prefetch index maps — the true software vindexmac:
indexed reads feeding the MAC loop) instead of gathering the pool into a
dense position-indexed copy first.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_FA = (512, 1024)   # (bq, bk)
_NEG = -1e30
# the paged decode kernels compute in f32 on the MXU at full f32 precision,
# as the XLA reference path does under "highest" matmul precision
_F32 = jax.lax.Precision.HIGHEST


def _fa_body(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
             scale: float, causal: bool, window: Optional[int],
             cap: Optional[float], bq: int, bk: int, k_steps: int,
             q_off: int, out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                  # [bq, d]
    k = k_ref[0].astype(jnp.float32)                  # [bk, d]
    v = v_ref[0].astype(jnp.float32)                  # [bk, dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)

    qpos = q_off + qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _store():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)[:, None]).astype(out_dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: Optional[int] = None,
                           cap: Optional[float] = None,
                           scale: Optional[float] = None,
                           block: Tuple[int, int] = DEFAULT_BLOCK_FA,
                           q_off: Optional[int] = None,
                           interpret: bool = False) -> jax.Array:
    """q [BH, Sq, D], k [BH, Sk, D], v [BH, Sk, Dv] -> [BH, Sq, Dv].
    Sq/Sk must divide by the block sizes (ops wrapper pads).

    ``q_off`` anchors the causal/window mask: query row i sits at absolute
    position ``q_off + i`` against keys at positions [0, Sk).  Default
    ``Sk - Sq`` (queries are the key suffix) — the semantics the bucketed
    prefill's forced-decode replay relies on (see module docstring)."""
    bh, sq, d = q.shape
    _, sk, dv = v.shape
    bq, bk = block
    scale = scale if scale is not None else d ** -0.5
    k_steps = sk // bk
    grid = (bh, sq // bq, k_steps)

    return pl.pallas_call(
        functools.partial(_fa_body, scale=scale, causal=causal, window=window,
                          cap=cap, bq=bq, bk=bk, k_steps=k_steps,
                          q_off=sk - sq if q_off is None else q_off,
                          out_dtype=q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# ===================================================== paged-decode attention
#
# Flash-decoding over the serve stack's paged KV pool (serve.paged.BlockPool):
# one query token per slot, K/V living in [n_blocks, block_size, ...] pools
# addressed through per-slot int32 block tables.  The gather path
# (models.attention._paged_update) materializes each slot's stream back into
# a dense [B, T*bs, ...] layout before the math — paying HBM for the whole
# table span per leaf per step.  These kernels instead walk the table INSIDE
# the kernel: the block table and per-slot kv lengths ride in as
# scalar-prefetch operands, so the BlockSpec index map resolves
# ``table[slot, j]`` to a physical [block_size, D] tile and the pipeline DMAs
# exactly the blocks a slot owns, while m/l/acc online-softmax state carries
# across the kv-block grid axis (same formulation as _fa_body above).  The
# grid spans the whole table.  In ``paged_gqa_decode`` a step whose block
# holds no valid position of its slot neither fetches nor computes: its index
# map clamps to the slot's last (or, windowed, first) valid block, so the
# pipeline sees an unchanged block index and issues no DMA, and its body is
# skipped — such a block would contribute exp(-inf) = 0.  ``paged_mla_decode``
# still fetches and masks every table column.  Inside a block, positions at
# and beyond ``kv_len`` (and, windowed, before the window) are masked.


def _paged_gqa_body(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, bs: int, t_steps: int,
                    scale: float, window: Optional[int],
                    cap: Optional[float]):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[b]
    live = j * bs < kv_len
    if window is not None:
        live &= (j + 1) * bs > kv_len - window

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)              # [KVH*G, KVH*d]
        k = k_ref[0].astype(jnp.float32)              # [bs, KVH*d]
        v = v_ref[0].astype(jnp.float32)              # [bs, KVH*dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32,
                                precision=_F32) * scale
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_len
        if window is not None:
            mask &= kpos > kv_len - 1 - window
        s = jnp.where(mask, s, _NEG)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=_F32)
        m_ref[...] = m_new

    @pl.when(j == t_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]


def _live_block(j, kv_len, bs: int, window: Optional[int]):
    """Table column to fetch at grid step ``j``: ``j`` clamped to the
    columns that hold a valid position of a slot with ``kv_len`` positions
    (the last ``window`` of them, windowed).  A step outside that range then
    names the same block as its neighbour, and the pipeline skips its DMA."""
    last = jnp.maximum((kv_len - 1) // bs, 0)
    if window is None:
        return jnp.minimum(j, last)
    first = jnp.maximum(kv_len - window, 0) // bs
    return jnp.minimum(jnp.maximum(j, first), last)


def paged_gqa_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_table: jax.Array, kv_len: jax.Array, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     cap: Optional[float] = None,
                     interpret: bool = False) -> jax.Array:
    """Fused paged GQA decode: q [B, KVH, G, d] (one token per slot, grouped
    by kv head), k_pool/v_pool [n_blocks, bs, KVH, d|dv], block_table int32
    [B, T], kv_len int32 [B] (valid positions per slot, current token
    included) -> [B, KVH, G, dv] float32.

    Grid (B, T): kv blocks are the innermost sequential axis; block j of
    slot b is fetched from physical block ``block_table[b, j]`` via the
    scalar-prefetched index map, so only pool blocks a slot's table names are
    ever read.  Only the blocks that hold a valid position of the slot
    (``ceil(kv_len / bs)`` of them, fewer under ``window``) are fetched and
    computed: the index map clamps every other step to a live block, which
    the pipeline does not fetch again, and the body skips it, so table
    entries past ``kv_len`` (trash, or stale) are never read.  One tile
    holds the block's tokens for every kv head — the pool viewed as
    [n_blocks, bs, KVH*d], a free reshape — which keeps the tile's last two
    dims whole, as Mosaic requires.  The heads are scored in one
    MXU pass with a block-diagonal query: row (h, g) carries q[h, g] in the
    lanes of kv head h and zeros elsewhere, so its scores and its slice of
    the context see only head h.  The context rows come back [KVH*G,
    KVH*dv], and the wrapper keeps each row's own head's lanes."""
    b, kvh, g, d = q.shape
    nb, bs = k_pool.shape[:2]
    dv = v_pool.shape[-1]
    t = block_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    rows = kvh * g
    eye = jnp.eye(kvh, dtype=q.dtype)
    q_bd = (q[:, :, :, None, :] * eye[None, :, None, :, None]).reshape(
        b, rows, kvh * d)

    def kv_map(b, j, tbl, lens):
        return (tbl[b, _live_block(j, lens[b], bs, window)], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec((1, rows, kvh * d), lambda b, j, tbl, lens: (b, 0, 0)),
            pl.BlockSpec((1, bs, kvh * d), kv_map),
            pl.BlockSpec((1, bs, kvh * dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, rows, kvh * dv),
                               lambda b, j, tbl, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows,), jnp.float32),
            pltpu.VMEM((rows, kvh * dv), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        functools.partial(_paged_gqa_body, bs=bs, t_steps=t, scale=scale,
                          window=window, cap=cap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rows, kvh * dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, kv_len, q_bd, k_pool.reshape(nb, bs, kvh * d),
      v_pool.reshape(nb, bs, kvh * dv))
    o = jnp.diagonal(o.reshape(b, kvh, g, kvh, dv), axis1=1, axis2=3)
    return jnp.moveaxis(o, -1, 1)                     # [B, KVH, G, dv]


def _paged_mla_body(tbl_ref, len_ref, ql_ref, qp_ref, c_ref, p_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, bs: int, t_steps: int,
                    scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ql = ql_ref[0].astype(jnp.float32)                # [H, r]
    qp = qp_ref[0].astype(jnp.float32)                # [H, rd]
    ckv = c_ref[0].astype(jnp.float32)                # [bs, r]
    kpe = p_ref[0].astype(jnp.float32)                # [bs, rd]
    # absorbed scores: latent + rope contributions, both against the pool
    s = (jax.lax.dot_general(ql, ckv, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=_F32)
         + jax.lax.dot_general(qp, kpe, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=_F32)) * scale

    kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < len_ref[b]
    s = jnp.where(mask, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=-1)
    # the value stream IS the latent cache (MLA's absorbed formulation)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, ckv, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        precision=_F32)
    m_ref[...] = m_new

    @pl.when(j == t_steps - 1)
    def _store():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]


def paged_mla_decode(q_lat: jax.Array, q_pe: jax.Array, ckv_pool: jax.Array,
                     kpe_pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, scale: float,
                     interpret: bool = False) -> jax.Array:
    """Fused paged MLA (absorbed) decode: q_lat [B, H, r] (queries already
    down-projected into the latent space), q_pe [B, H, rd], ckv_pool
    [n_blocks, bs, r], kpe_pool [n_blocks, bs, rd], block_table int32 [B, T],
    kv_len int32 [B] -> latent context [B, H, r] float32 (caller up-projects
    through wuv).

    Same online-softmax-over-table-walk as paged_gqa_decode, with the MLA
    twist that scores sum a latent and a rope dot and the value operand is
    the latent cache itself — the whole kernel runs in the compressed
    kv_lora space (SNIPPETS.md Snippet 3's mla_decode formulation)."""
    b, h, r = q_lat.shape
    rd = q_pe.shape[-1]
    nb, bs = ckv_pool.shape[:2]
    t = block_table.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, t),
        in_specs=[
            pl.BlockSpec((1, h, r), lambda b, j, tbl, lens: (b, 0, 0)),
            pl.BlockSpec((1, h, rd), lambda b, j, tbl, lens: (b, 0, 0)),
            pl.BlockSpec((1, bs, r),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0)),
            pl.BlockSpec((1, bs, rd),
                         lambda b, j, tbl, lens: (tbl[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, r), lambda b, j, tbl, lens: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h,), jnp.float32),
            pltpu.VMEM((h, r), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_mla_body, bs=bs, t_steps=t, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, r), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, kv_len, q_lat, q_pe, ckv_pool, kpe_pool)


def paged_gqa_verify(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_table: jax.Array, kv_len: jax.Array, *,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     cap: Optional[float] = None,
                     interpret: bool = False) -> jax.Array:
    """Fused paged GQA over a span of S queries per slot: q [B, S, KVH, G, d]
    at consecutive positions, kv_len int32 [B] valid positions for the FIRST
    query (its own token included) -> [B, S, KVH, G, dv] float32.

    The speculative verify step scores k+1 positions against the pool after
    the span's K/V have been written.  Query offset i sees exactly
    ``kv_len + i`` positions (causal within the span), so each offset is one
    ``paged_gqa_decode`` launch over the same table — the single-query kernel
    is reused verbatim, which keeps offset 0 of a 1-query span bitwise equal
    to the plain decode step."""
    s = q.shape[1]
    outs = [paged_gqa_decode(q[:, i], k_pool, v_pool, block_table,
                             kv_len + i, scale=scale, window=window, cap=cap,
                             interpret=interpret)
            for i in range(s)]
    return jnp.stack(outs, axis=1)


def paged_mla_verify(q_lat: jax.Array, q_pe: jax.Array, ckv_pool: jax.Array,
                     kpe_pool: jax.Array, block_table: jax.Array,
                     kv_len: jax.Array, *, scale: float,
                     interpret: bool = False) -> jax.Array:
    """Fused paged MLA (absorbed) over a span of S queries per slot:
    q_lat [B, S, H, r], q_pe [B, S, H, rd], kv_len int32 [B] valid positions
    for the first query -> latent context [B, S, H, r] float32.  Query offset
    i attends to ``kv_len + i`` positions; see ``paged_gqa_verify``."""
    s = q_lat.shape[1]
    outs = [paged_mla_decode(q_lat[:, i], q_pe[:, i], ckv_pool, kpe_pool,
                             block_table, kv_len + i, scale=scale,
                             interpret=interpret)
            for i in range(s)]
    return jnp.stack(outs, axis=1)


def paged_decode_traffic(b: int, table_width: int, block_size: int,
                         kv_lens, d: int, dv: int, *,
                         dtype_bytes: int = 2) -> dict:
    """Per-step KV HBM traffic model, fused vs gather (for BENCH_5 and the
    roofline): the gather path materializes every slot's full table span as a
    dense copy (pool read + copy write + attention read = 3 passes over
    T*bs positions per slot); the fused walk reads each owned block once —
    ceil(kv_len/bs)*bs positions per slot, no copy."""
    span = table_width * block_size
    per_pos = (d + dv) * dtype_bytes
    gather = 3 * b * span * per_pos
    fused = sum(-(-int(l) // block_size) * block_size for l in kv_lens) \
        * per_pos
    return dict(gather_bytes=gather, fused_bytes=fused,
                ratio=fused / max(gather, 1))


def flash_traffic(bh: int, sq: int, sk: int, d: int, dv: int, *,
                  dtype_bytes: int = 2,
                  block: Tuple[int, int] = DEFAULT_BLOCK_FA) -> dict:
    """HBM traffic model (for the roofline's kernel adjustment): q read once
    per kv sweep is amortized (stays in VMEM across the inner axis); k/v
    re-streamed per q block; scores NEVER touch HBM — that is the point."""
    bq, bk = block
    q_bytes = bh * sq * d * dtype_bytes
    kv_bytes = (sq // bq) * bh * sk * (d + dv) * dtype_bytes
    out_bytes = bh * sq * dv * dtype_bytes
    flops = 2.0 * bh * sq * sk * (d + dv)
    return dict(hbm_bytes=q_bytes + kv_bytes + out_bytes, flops=flops,
                q_bytes=q_bytes, kv_bytes=kv_bytes, out_bytes=out_bytes)
