"""N:M structured sparsity format — the paper's data representation.

A matrix is N:M structured-sparse along its *last* axis when every
consecutive block of M elements contains at most N non-zeros (paper Fig 1b).
The compressed representation stores, per block, exactly N (value, col_idx)
pairs where col_idx is the *in-block* position in [0, M) — the paper's few-bit
``col_idx`` stream.  Full column indices are reconstructed on the fly as
``block_id * M + col_idx`` (paper Fig 3 / Alg 3-S line 8).

Slot-planar order: with NB = in_dim // M blocks, compressed entry
``s * NB + b`` holds slot s of block b.  Each slot is then one contiguous,
lane-dense run, which is what lets the TPU kernels read it without
interleaving lanes (kernels/nm_spmm.py).

Layout convention: a weight W used as ``y = x @ W.T`` has shape [out, in] and
is sparsified along ``in`` (the contraction axis) — W plays the role of the
paper's sparse matrix A, x.T the dense matrix B.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NMSparse",
    "nm_mask",
    "sparsify",
    "compress",
    "decompress",
    "pack_indices",
    "unpack_indices",
    "storage_bytes",
    "validate_nm",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class NMSparse:
    """Compressed N:M sparse tensor (sparse along the last dense axis).

    values:  [..., rows, nnz] with nnz = in_dim // m * n   (slot-planar
             order: entry j is slot j // NB of block j % NB, NB = in_dim // m)
    indices: int8 [..., rows, nnz], each in [0, m) — in-block column index,
             strictly increasing over a block's n slots.
    """

    values: jax.Array
    indices: jax.Array
    n: int = dataclasses.field(metadata=dict(static=True))
    m: int = dataclasses.field(metadata=dict(static=True))
    dense_shape: Tuple[int, ...] = dataclasses.field(metadata=dict(static=True))

    @property
    def nnz_per_row(self) -> int:
        return self.dense_shape[-1] // self.m * self.n

    @property
    def num_blocks(self) -> int:
        return self.dense_shape[-1] // self.m

    @property
    def dtype(self):
        return self.values.dtype

    def astype(self, dtype) -> "NMSparse":
        return NMSparse(self.values.astype(dtype), self.indices, self.n, self.m,
                        self.dense_shape)


def _check_nm(in_dim: int, n: int, m: int) -> None:
    if not (0 < n < m):
        raise ValueError(f"need 0 < N < M, got {n}:{m}")
    if in_dim % m != 0:
        raise ValueError(f"last axis {in_dim} not divisible by block size M={m}")


def nm_mask(w: jax.Array, n: int, m: int) -> jax.Array:
    """Top-|N| magnitude mask per M-block along the last axis (exact N per
    block, ties broken toward the lower index — same order as top_k).

    For small M this uses a rank-by-pairwise-comparison formulation instead
    of top_k: top_k lowers to a sort that GSPMD cannot partition (it
    all-gathers the operand — for a 480B MoE that is an 18 GB replicated
    tensor per training step).  The pairwise form is pure elementwise ops and
    stays sharded.
    """
    _check_nm(w.shape[-1], n, m)
    blocks = w.reshape(*w.shape[:-1], w.shape[-1] // m, m)
    if m <= 8:
        a = jnp.abs(blocks)
        ai = a[..., :, None]                           # [..., nb, m, 1]
        aj = a[..., None, :]                           # [..., nb, 1, m]
        ii = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
        ahead = (aj > ai) | ((aj == ai) & (jj < ii))   # j outranks i
        rank = ahead.sum(-1)                           # [..., nb, m]
        mask = rank < n
        return mask.reshape(w.shape)
    _, idx = jax.lax.top_k(jnp.abs(blocks).astype(jnp.float32), n)  # [..., nb, n]
    onehot = jax.nn.one_hot(idx, m, dtype=jnp.bool_)                # [..., nb, n, m]
    mask = jnp.any(onehot, axis=-2)                                 # [..., nb, m]
    return mask.reshape(w.shape)


def sparsify(w: jax.Array, n: int, m: int) -> jax.Array:
    """Dense -> dense with N:M pattern enforced (magnitude pruning)."""
    return w * nm_mask(w, n, m).astype(w.dtype)


def top_planes(planes, keys, keep: int, key_dtype):
    """Elementwise over equal-shape ``planes``: the ``keep`` of largest
    magnitude (ties to the lower plane), in plane order, with their ``keys``
    (arrays or scalars).  Returns ``keep`` value planes and ``keep`` key
    planes of ``key_dtype``.

    Only elementwise ops on the planes: no sort, no gather, and no minor
    axis of length M, which a TPU pads to a whole 128-lane row (32x the
    operand at M = 4)."""
    mags = [jnp.abs(p).astype(jnp.float32) for p in planes]
    shape = planes[0].shape
    vals = [jnp.zeros(shape, planes[0].dtype) for _ in range(keep)]
    ids = [jnp.zeros(shape, key_dtype) for _ in range(keep)]
    slot = jnp.zeros(shape, jnp.int32)      # kept planes so far
    for i, (p, key) in enumerate(zip(planes, keys)):
        rank = sum(((mags[j] >= mags[i]) if j < i else (mags[j] > mags[i])
                    ).astype(jnp.int32) for j in range(len(planes)) if j != i)
        kept = rank < keep
        for s in range(keep):
            hit = kept & (slot == s)
            vals[s] = jnp.where(hit, p, vals[s])
            ids[s] = jnp.where(hit, key, ids[s]).astype(key_dtype)
        slot = slot + kept.astype(jnp.int32)
    return vals, ids


@partial(jax.jit, static_argnames=("n", "m"))
def _compress_planar(w: jax.Array, n: int, m: int):
    # position planes: plane c holds in-block column c of every block
    vals, ids = top_planes([w[..., c::m] for c in range(m)], range(m), n,
                           jnp.int8)
    return jnp.concatenate(vals, -1), jnp.concatenate(ids, -1)


def compress(w: jax.Array, n: int, m: int) -> NMSparse:
    """Dense [..., in] -> compressed (top-N magnitude per block, index-sorted).

    The kept entries within each block are ordered by ascending in-block
    column index, matching the paper's memory layout where col_idx words are
    streamed in order (Alg 3-S), and stored slot-planar.
    """
    _check_nm(w.shape[-1], n, m)
    values, indices = _compress_planar(w, n, m)
    return NMSparse(values=values, indices=indices, n=n, m=m,
                    dense_shape=tuple(w.shape))


def decompress(sp: NMSparse) -> jax.Array:
    """Compressed -> dense.  One-hot scatter per block: the vectorized
    equivalent of the paper's ``block_id*M + col_idx`` reconstruction."""
    lead = sp.dense_shape[:-1]
    nb, n, m = sp.num_blocks, sp.n, sp.m
    vals = sp.values.reshape(*lead, n, nb)
    idx = sp.indices.reshape(*lead, n, nb).astype(jnp.int32)
    onehot = jax.nn.one_hot(idx, m, dtype=sp.values.dtype)      # [..., n, nb, m]
    dense = jnp.einsum("...nb,...nbm->...bm", vals, onehot)
    return dense.reshape(sp.dense_shape)


# ---------------------------------------------------------------------------
# 2-bit index packing — the paper's storage accounting (Fig 9 / §IV-B): the
# structured format stores ceil(log2 M)-bit indices; full-column CSR-like
# indices cost 14.7–26.5 % extra storage on their layers.
# ---------------------------------------------------------------------------

def _bits_per_index(m: int) -> int:
    return max(1, int(np.ceil(np.log2(m))))


def pack_indices(indices: jax.Array, m: int) -> jax.Array:
    """int8 in-block indices -> packed uint32 words along the last axis."""
    bits = _bits_per_index(m)
    per_word = 32 // bits
    nnz = indices.shape[-1]
    pad = (-nnz) % per_word
    idx = jnp.pad(indices.astype(jnp.uint32), [(0, 0)] * (indices.ndim - 1) + [(0, pad)])
    idx = idx.reshape(*indices.shape[:-1], -1, per_word)
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits)
    return jnp.sum(idx << shifts, axis=-1, dtype=jnp.uint32)


def unpack_indices(packed: jax.Array, m: int, nnz: int) -> jax.Array:
    """Packed uint32 words -> int8 in-block indices (inverse of pack_indices)."""
    bits = _bits_per_index(m)
    per_word = 32 // bits
    shifts = (jnp.arange(per_word, dtype=jnp.uint32) * bits)
    idx = (packed[..., None] >> shifts) & jnp.uint32((1 << bits) - 1)
    idx = idx.reshape(*packed.shape[:-1], -1)[..., :nnz]
    return idx.astype(jnp.int8)


def storage_bytes(sp: NMSparse, packed: bool = True,
                  full_column: bool = False) -> int:
    """Bytes to store the compressed tensor.

    packed=True uses ceil(log2 M)-bit indices (the paper's format);
    full_column=True models the Alg-3S-FC baseline (full-width column ids).
    """
    nvals = int(np.prod(sp.values.shape))
    val_bytes = nvals * sp.values.dtype.itemsize
    if full_column:
        idx_bytes = nvals * 4                         # int32 column ids
    elif packed:
        idx_bytes = int(np.ceil(nvals * _bits_per_index(sp.m) / 8))
    else:
        idx_bytes = nvals                             # int8
    return val_bytes + idx_bytes


def validate_nm(w_or_sp, n: int | None = None, m: int | None = None) -> bool:
    """True iff the argument satisfies the N:M constraint.

    Accepts a dense array (requires n, m) or an NMSparse (checks index
    invariants: in range, strictly increasing within each block).
    """
    if isinstance(w_or_sp, NMSparse):
        sp = w_or_sp
        lead = sp.dense_shape[:-1]
        idx = np.asarray(sp.indices).reshape(*lead, sp.n, sp.num_blocks)
        in_range = bool(((idx >= 0) & (idx < sp.m)).all())
        increasing = bool((np.diff(idx, axis=-2) > 0).all()) if sp.n > 1 else True
        return in_range and increasing
    w, = (np.asarray(w_or_sp),)
    assert n is not None and m is not None
    blocks = w.reshape(*w.shape[:-1], w.shape[-1] // m, m)
    return bool(((blocks != 0).sum(axis=-1) <= n).all())
