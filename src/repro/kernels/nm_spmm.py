"""Pallas TPU kernel: N:M structured-sparse x dense matmul.

TPU adaptation of the paper's vindexmac dataflow:

  * the dense operand tile is pinned in VMEM by its BlockSpec — the analogue
    of preloading L rows of B into the vector register file (Alg 5/6);
  * the compressed A tile (values + bounded in-block indices) is decompressed
    *inside VMEM* — every indirect access implied by the sparse format is a
    local read, never an HBM gather (the vindexmac property);
  * the MXU then consumes dense tiles.  HBM traffic for A is the compressed
    stream (values * N/M of dense + 2-bit indices), which is the paper's
    Fig 12 memory-access reduction.

Layout (``core.sparsity``): compressed rows are *slot-planar* — entry
``s * NB + b`` is slot ``s`` of M-block ``b`` — so the n slots of a K-tile
are n contiguous, lane-dense runs.  The kernel reads them as n separate
BlockSpecs of the same array.  Mosaic cannot interleave lanes, so instead of
scattering each slot back to column ``b*M + c``, the kernel builds M
*position planes*: plane c holds, for every M-block, the weight at in-block
column c.  The dense operand is de-interleaved to match outside the kernel
(``_planes_of``: a [.., K] -> [M, .., K/M] transpose of the small operand),
and the product is the sum over c of plane c times the dense operand's
plane c.  Every temporary is a 2-D [rows, K/M-tile] array with a 128-lane
minor dimension.

Two orientations are provided:
  nm_spmm_kernel : C = A_sp @ B          (paper's A x B, Fig 2)
  nm_xwt_kernel  : Y = X  @ A_sp.T       (layer forward y = x @ W.T)
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = (128, 128, 512)  # (bm, bn, bk)


def _unpack_indices_tile(packed, n: int, m: int, bnnz: int):
    """uint32 packed words [rows, bnnz/per_word] -> int32 indices [rows, bnnz].

    The paper stores ceil(log2 M)-bit col_idx words (Fig 1b / §IV-B storage);
    this is the in-VMEM shift/mask unpack, kept 2-D: each word is broadcast
    per_word-wide, then right-shifted by its slot's bit offset (vectorized
    variable shift on the VPU).
    """
    bits = max(1, int(np.ceil(np.log2(m))))
    per_word = 32 // bits
    rows = packed.shape[0]
    words = jnp.repeat(packed, per_word, axis=1)[:, :bnnz]   # [rows, bnnz]
    slot = jax.lax.broadcasted_iota(jnp.uint32, (rows, bnnz), 1) % per_word
    return ((words >> (slot * bits)) & ((1 << bits) - 1)).astype(jnp.int32)


def _position_planes(val_refs, idx_refs, n: int, m: int, dtype,
                     packed: bool = False):
    """n slot tiles [rows, nbt] -> m position planes [rows, nbt] in ``dtype``.

    plane[c][r, b] = sum_s vals_s[r, b] * (idx_s[r, b] == c): the weight of
    row r at column ``b*M + c`` of the tile — the paper's ``block_id*M +
    col_idx`` reconstruction (Fig 3) with the block id left implicit in the
    lane position.  Summed in f32, so a duplicate index adds like the oracle.
    """
    vals = [v[...].astype(jnp.float32) for v in val_refs]
    nbt = vals[0].shape[1]
    idxs = [_unpack_indices_tile(i[...], n, m, nbt) if packed
            else i[...].astype(jnp.int32) for i in idx_refs]
    planes = []
    for c in range(m):            # static: m <= 8 in all supported patterns
        w = jnp.zeros_like(vals[0])
        for s in range(n):
            w = w + jnp.where(idxs[s] == c, vals[s], 0.0)
        planes.append(w.astype(dtype))
    return planes


def _planes_of(a: jax.Array, m: int, axis: int) -> jax.Array:
    """De-interleave ``axis`` (length K) of a dense operand into M planes:
    [.., K, ..] -> [M, .., K/M, ..], plane c holding positions b*M + c."""
    k = a.shape[axis]
    split = a.shape[:axis] + (k // m, m) + a.shape[axis + 1:]
    return jnp.moveaxis(a.reshape(split), axis + 1, 0)


def _dot_dtype(*dtypes):
    return functools.reduce(jnp.promote_types, dtypes)


def _xwt_body(x_ref, *refs, n: int, m: int, k_steps: int, dtype,
              packed: bool):
    """Y[i,j] tile += sum_c X_c[i,k] @ plane_c(W[j,k]).T  (contract on k)."""
    val_refs, idx_refs = refs[:n], refs[n:2 * n]
    out_ref, acc_ref = refs[2 * n:]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    planes = _position_planes(val_refs, idx_refs, n, m, dtype, packed)
    acc = acc_ref[...]
    for c in range(m):
        acc += jax.lax.dot_general(
            x_ref[c].astype(dtype), planes[c],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _spmm_body(*refs, n: int, m: int, k_steps: int, dtype):
    """C[i,j] tile += sum_c plane_c(A[i,k]) @ B_c[k,j]."""
    val_refs, idx_refs = refs[:n], refs[n:2 * n]
    b_ref, out_ref, acc_ref = refs[2 * n:]

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    planes = _position_planes(val_refs, idx_refs, n, m, dtype)
    acc = acc_ref[...]
    for c in range(m):
        acc += jax.lax.dot_general(
            planes[c], b_ref[c].astype(dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _check_block(block: Tuple[int, int, int], n: int, m: int):
    bm, bn, bk = block
    if bk % m:
        raise ValueError(f"bk={bk} must be a multiple of M={m}")
    return bm, bn, bk


def _slot_specs(n: int, rows: int, width: int, k_steps: int, row_of):
    """One BlockSpec per slot over a slot-planar [R, n * k_steps * width]
    array: slot s of K-step kk is the lane block ``s * k_steps + kk``."""
    return [pl.BlockSpec((rows, width),
                         lambda i, j, kk, s=s: (row_of(i, j),
                                                s * k_steps + kk))
            for s in range(n)]


def nm_spmm_kernel(values: jax.Array, indices: jax.Array, b: jax.Array,
                   n: int, m: int, *, block: Tuple[int, int, int] = DEFAULT_BLOCK,
                   out_dtype=None, interpret: bool = False) -> jax.Array:
    """C = A_sp @ B.  values/indices slot-planar [R, K//M*N] (pre-padded to
    block multiples by ops.py), b [K, C]."""
    bm, bn, bk = _check_block(block, n, m)
    r, nnz = values.shape
    k, c = b.shape
    assert nnz == k // m * n, (values.shape, b.shape, n, m)
    nbt = bk // m
    k_steps = k // bk
    dtype = _dot_dtype(values.dtype, b.dtype)
    slot = functools.partial(_slot_specs, n, bm, nbt, k_steps,
                             lambda i, j: i)
    return pl.pallas_call(
        functools.partial(_spmm_body, n=n, m=m, k_steps=k_steps, dtype=dtype),
        grid=(r // bm, c // bn, k_steps),
        in_specs=[*slot(), *slot(),
                  pl.BlockSpec((m, nbt, bn), lambda i, j, kk: (0, kk, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype or b.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*[values] * n, *[indices] * n, _planes_of(b, m, 0))


def nm_xwt_kernel(x: jax.Array, values: jax.Array, indices: jax.Array,
                  n: int, m: int, *, block: Tuple[int, int, int] = DEFAULT_BLOCK,
                  out_dtype=None, interpret: bool = False,
                  packed: bool = False) -> jax.Array:
    """Y = X @ W_sp.T.  x [B, K], values slot-planar [O, K//M*N] (pre-padded).

    packed=False: indices int8 [O, K//M*N].
    packed=True:  indices uint32, each (slot, K-step) run of bk//M indices
    packed into whole words — the paper's bit-packed col_idx stream,
    unpacked inside VMEM (HBM index bytes drop 4x at M=4)."""
    bm, bn, bk = _check_block(block, n, m)
    bsz, k = x.shape
    o, nnz_cols = values.shape
    assert nnz_cols == k // m * n, (x.shape, values.shape, n, m)
    nbt = bk // m
    k_steps = k // bk
    idx_width = nbt
    if packed:
        per_word = 32 // max(1, int(np.ceil(np.log2(m))))
        assert nbt % per_word == 0, (nbt, per_word)
        idx_width = nbt // per_word
    dtype = _dot_dtype(x.dtype, values.dtype)
    row_of = lambda i, j: j                                    # noqa: E731
    return pl.pallas_call(
        functools.partial(_xwt_body, n=n, m=m, k_steps=k_steps, dtype=dtype,
                          packed=packed),
        grid=(bsz // bm, o // bn, k_steps),
        in_specs=[pl.BlockSpec((m, bm, nbt), lambda i, j, kk: (0, i, kk)),
                  *_slot_specs(n, bn, nbt, k_steps, row_of),
                  *_slot_specs(n, bn, idx_width, k_steps, row_of)],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, o), out_dtype or x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_planes_of(x, m, 1), *[values] * n, *[indices] * n)
