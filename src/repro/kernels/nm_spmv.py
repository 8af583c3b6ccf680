"""Pallas TPU kernel: N:M sparse matrix x small dense batch (decode regime).

The paper's Algorithm 6 on the TPU: the activation matrix x (the "tile of
B") is resident in VMEM across the whole row sweep, and every access the
sparse format implies is an *indirect local read* — the vindexmac dataflow.
Decode is memory-bound on the weight stream, so the kernel's win is the
compressed A traffic (values N/M of dense + 2-bit indices).

The body is ``nm_xwt``'s (decompress into position planes in VMEM, then MXU
dots): the batch is one row tile, and the weight tiles are wider, because a
decode step streams every weight exactly once.
"""

from __future__ import annotations

from typing import Tuple

import jax

from repro.kernels.nm_spmm import nm_xwt_kernel

DEFAULT_BLOCK_SPMV = (128, 1024)  # (bo, bk)


def nm_spmv_kernel(x: jax.Array, values: jax.Array, indices: jax.Array,
                   n: int, m: int, *,
                   block: Tuple[int, int] = DEFAULT_BLOCK_SPMV,
                   out_dtype=None, interpret: bool = False) -> jax.Array:
    """Y = X @ W_sp.T with X a small batch [B, K]; W compressed slot-planar
    [O, K//M*N].

    All dims pre-padded to block multiples by ops.py.  The batch is not tiled
    (decode batches are small); the grid is (1, O tiles, K steps) and x's
    BlockSpec keeps the current K-slice resident across the O sweep.
    """
    bo, bk = block
    return nm_xwt_kernel(x, values, indices, n, m, block=(x.shape[0], bo, bk),
                         out_dtype=out_dtype, interpret=interpret)
