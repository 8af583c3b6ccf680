"""Pure-jnp oracles for the N:M sparse matmul kernels.

These are the ground truth that every Pallas kernel (and every fast XLA
formulation) is validated against.  They are deliberately written in the most
obvious way: decompress to dense, then a dense contraction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def nm_decompress_ref(values: jax.Array, indices: jax.Array, n: int, m: int,
                      k: int) -> jax.Array:
    """[rows, nnz] slot-planar values + int8 in-block indices -> dense
    [rows, k]."""
    rows, nnz = values.shape
    assert nnz == k // m * n, (values.shape, n, m, k)
    nb = k // m
    vals = values.reshape(rows, n, nb)
    idx = indices.reshape(rows, n, nb).astype(jnp.int32)
    onehot = jax.nn.one_hot(idx, m, dtype=values.dtype)       # [rows, n, nb, m]
    dense = jnp.einsum("rnb,rnbm->rbm", vals, onehot)
    return dense.reshape(rows, k)


def nm_spmm_ref(values: jax.Array, indices: jax.Array, b: jax.Array,
                n: int, m: int) -> jax.Array:
    """Paper orientation: C = A_sparse @ B.  A compressed [R, nnz], B [K, C]."""
    k = b.shape[0]
    a = nm_decompress_ref(values, indices, n, m, k)
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32)).astype(b.dtype)


def nm_xwt_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
               n: int, m: int) -> jax.Array:
    """Layer orientation: Y = X @ W_sparse.T.  X [..., K], W compressed [O, nnz]."""
    k = x.shape[-1]
    w = nm_decompress_ref(values, indices, n, m, k)
    y = jnp.einsum("...k,ok->...o", x.astype(jnp.float32), w.astype(jnp.float32))
    return y.astype(x.dtype)


def nm_spmv_ref(x: jax.Array, values: jax.Array, indices: jax.Array,
                n: int, m: int) -> jax.Array:
    """Decode orientation (vindexmac-faithful): Y[b, o] = sum_e vals[o, e] *
    x[b, block(e)*M + idx[o, e]] — an explicit gather-MAC, numerically equal
    to nm_xwt_ref but expressed the way Algorithm 6 executes it."""
    o, nnz = values.shape
    blk = (jnp.arange(nnz, dtype=jnp.int32) % (nnz // n)) * m  # block base
    full_idx = blk[None, :] + indices.astype(jnp.int32)      # [o, nnz]
    gathered = x.astype(jnp.float32)[:, full_idx]            # [b, o, nnz]
    y = jnp.einsum("boe,oe->bo", gathered, values.astype(jnp.float32))
    return y.astype(x.dtype)
