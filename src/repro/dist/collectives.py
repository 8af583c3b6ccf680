"""Overlapped collective matmuls (ring all-gather fused into the matmul).

Instead of all-gathering the sharded operand and then multiplying (a serial
dependency: the matmul waits for the full gather), each device multiplies
the shard it currently holds while collective-permuting it to its ring
neighbor — the classic "collective matmul" overlap.  The compiled HLO must
contain ``collective-permute`` and no ``all-gather`` (asserted by
tests/test_collective_matmul.py).

``collective_matmul_ag_sparse`` is the distributed analogue of the paper's
Fig 12 memory-traffic reduction: the *compressed* N:M shard (values + few-bit
in-block indices) is what rotates around the ring; every device decompresses
locally right before its MXU consumes the tile.  Per ring step the wire
carries N/M of the dense value bytes (+ a 2-bit index stream) — see
``ring_step_bytes`` for the analytic accounting used by the traffic tests.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.sparsity import (NMSparse, _bits_per_index, decompress,
                                 pack_indices, unpack_indices)


def _ring_perm(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def collective_matmul_ag(x: jax.Array, w: jax.Array, axis_name: str
                         ) -> jax.Array:
    """y_local = x_full @ w_local without materializing x_full.

    Per-device operands (inside shard_map over ``axis_name``, size n):
      x: [B, K/n]   — this device's shard of the contraction axis;
      w: [K, O/n]   — full contraction axis, local output columns.
    Returns y: [B, O/n].

    Each of the n steps multiplies the currently-held x shard against the
    matching K-rows of w and rotates the shard one hop; the permutes of step
    i overlap the matmul of step i (XLA schedules them concurrently since
    neither depends on the other's output).
    """
    n = lax.psum(1, axis_name)          # static under shard_map
    idx = lax.axis_index(axis_name)
    chunk = x.shape[-1]
    perm = _ring_perm(n)
    acc = jnp.zeros((x.shape[0], w.shape[-1]),
                    jnp.promote_types(x.dtype, w.dtype))
    xb = x
    for i in range(n):
        src = (idx - i) % n             # origin device of the held shard
        wk = lax.dynamic_slice_in_dim(w, src * chunk, chunk, axis=0)
        acc = acc + xb @ wk
        if i != n - 1:
            xb = lax.ppermute(xb, axis_name, perm)
    return acc


def collective_matmul_ag_sparse(values: jax.Array, indices: jax.Array,
                                x: jax.Array, axis_name: str,
                                n: int, m: int) -> jax.Array:
    """y = x @ decompress(W_sp).T with only the compressed shards on the wire.

    Per-device operands (inside shard_map over ``axis_name``, size ndev):
      values:  [O/ndev, K//m*n]  — compressed N:M values of the output rows
      indices: [O/ndev, K//m*n]  — int8 in-block column indices
      x:       [B, K]            — replicated dense activation
    Returns y: [B, O] (identical on every device: all shards rotate through).

    Only values + bit-packed indices are permuted — N/m of the dense value
    bytes plus a ceil(log2 m)-bit/nonzero index stream per step (the paper's
    compressed format, kept compressed across the network; ring_step_bytes
    with packed=True is the matching accounting).  Unpack + decompress are
    local, immediately before the dot.
    """
    ndev = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    o_shard, nnz = values.shape
    k = x.shape[-1]
    perm = _ring_perm(ndev)
    y = jnp.zeros((x.shape[0], o_shard * ndev),
                  jnp.promote_types(x.dtype, values.dtype))
    vb = values
    ib = pack_indices(indices, m)       # the few-bit stream is what rotates
    for i in range(ndev):
        src = (idx - i) % ndev
        w_dense = decompress(
            NMSparse(vb, unpack_indices(ib, m, nnz), n, m, (o_shard, k)))
        y = lax.dynamic_update_slice_in_dim(
            y, (x @ w_dense.T).astype(y.dtype), src * o_shard, axis=1)
        if i != ndev - 1:
            vb = lax.ppermute(vb, axis_name, perm)
            ib = lax.ppermute(ib, axis_name, perm)
    return y


def ring_step_bytes(o_shard: int, k: int, n: int = 2, m: int = 4, *,
                    dtype_bytes: int = 2, sparse: bool = True,
                    packed: bool = True) -> Dict[str, int]:
    """Bytes one device puts on the wire per ring step.

    Dense rotation would move o_shard*k values; the compressed rotation moves
    o_shard*(k//m)*n values plus the ceil(log2 m)-bit index stream (packed)
    or int8 indices (unpacked) — mirroring kernels.ops.traffic_mm's per-element
    accounting so the single-chip and cross-chip traffic models agree.
    """
    if not sparse:
        dense = o_shard * k * dtype_bytes
        return dict(value_bytes=dense, index_bytes=0, total_bytes=dense)
    nnz = o_shard * (k // m) * n
    value_bytes = nnz * dtype_bytes
    if packed:
        index_bytes = int(np.ceil(nnz * _bits_per_index(m) / 8))
    else:
        index_bytes = nnz               # int8 stream
    return dict(value_bytes=value_bytes, index_bytes=index_bytes,
                total_bytes=value_bytes + index_bytes)


def ring_matmul_bytes(o: int, k: int, ndev: int, n: int = 2, m: int = 4, *,
                      dtype_bytes: int = 2, sparse: bool = True,
                      packed: bool = True) -> int:
    """Total wire bytes for one full ring matmul (all devices, all steps).

    Every device rotates its held shard ndev-1 times, so the ring moves
    ndev*(ndev-1) shard-transfers of ring_step_bytes each.  With sparse=False
    this models the dense-weight ring (the baseline the compressed ring is
    compared against in benchmarks/serve_dist.py).
    """
    per_step = ring_step_bytes(o // ndev, k, n, m, dtype_bytes=dtype_bytes,
                               sparse=sparse, packed=packed)["total_bytes"]
    return ndev * (ndev - 1) * per_step


def ring_sparse_linear(x: jax.Array, values: jax.Array, indices: jax.Array,
                       n: int, m: int, mesh, axis: str = "model"
                       ) -> jax.Array:
    """y = x @ decompress(values, indices).T via the explicit sparse ring.

    Jit-level wrapper around ``collective_matmul_ag_sparse``: takes the
    *global* compressed operands (values/indices ``[..., O, nnz]`` sharded or
    shardable on O over ``axis``), flattens x's leading dims, runs the
    shard_map'd ring, and restores the leading dims.  Bitwise-equal to the
    local ``_xwt_xla`` path because every device computes x @ w_dense.T for
    each shard with the same contraction order.
    """
    from jax.sharding import PartitionSpec as P
    o = values.shape[-2]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    f = jax.shard_map(
        lambda v, i, xl: collective_matmul_ag_sparse(v, i, xl, axis, n, m),
        mesh=mesh, in_specs=(P(axis), P(axis), P()), out_specs=P(),
        check_vma=False)
    y = f(values, indices, x2)
    return y.reshape(*lead, o).astype(x.dtype)
