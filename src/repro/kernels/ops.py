"""jit'd public wrappers around the N:M Pallas kernels.

Handles leading-dim flattening, padding to block multiples, adaptive block
selection, and provides the analytic HBM-traffic model used by the roofline
(cost_analysis cannot see inside pallas_call, so kernel traffic is modeled
from the BlockSpecs — deterministically, per DESIGN.md §2/§5).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import nm_spmm as _spmm
from repro.kernels import nm_spmv as _spmv


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def pallas_interpret() -> bool:
    """Pallas kernels run natively on a TPU and in interpret mode on any other
    backend (the CPU test path).  Resolved at trace time."""
    return jax.default_backend() != "tpu"


def _pad_axis(x: jax.Array, axis: int, target: int) -> jax.Array:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    cfg = [(0, 0)] * x.ndim
    cfg[axis] = (0, pad)
    return jnp.pad(x, cfg)


def _pad_slots(a: jax.Array, rows: int, n: int, nb: int) -> jax.Array:
    """Pad a slot-planar [R, n * NB] array to [rows, n * nb]: each slot's run
    of NB entries is padded to nb (zero values, index 0) on its own."""
    r, width = a.shape
    a = a.reshape(r, n, width // n)
    return _pad_axis(_pad_axis(a, 0, rows), 2, nb).reshape(rows, n * nb)


def _k_block(k: int, m: int, want: int) -> int:
    """K-tile: a multiple of 128 M-blocks, so each slot's run of the tile
    fills whole 128-lane rows (the Mosaic block-shape rule)."""
    return min(_round_up(want, 128 * m), _round_up(k, 128 * m))


def pick_block_mm(bsz: int, o: int, k: int, n: int, m: int,
                  want: Tuple[int, int, int] = _spmm.DEFAULT_BLOCK):
    """Block sizes for the matmul kernels; shrinks for small problems."""
    bm = min(want[0], _round_up(bsz, 8))
    bn = min(want[1], _round_up(o, 128) if o >= 128 else o)
    return bm, bn, _k_block(k, m, want[2])


def pick_block_spmv(bsz: int, o: int, k: int, n: int, m: int,
                    want: Tuple[int, int] = _spmv.DEFAULT_BLOCK_SPMV):
    bo = min(want[0], _round_up(o, 128) if o >= 128 else o)
    return bo, _k_block(k, m, want[1])


def _padded_operands(values, indices, o_mult: int, k: int, bk: int,
                     n: int, m: int):
    o, _ = values.shape
    op, kp = _round_up(o, o_mult), _round_up(k, bk)
    return (_pad_slots(values, op, n, kp // m),
            _pad_slots(indices, op, n, kp // m), op, kp)


@functools.partial(jax.jit,
                   static_argnames=("n", "m", "block", "interpret", "packed"))
def nm_xwt(x: jax.Array, values: jax.Array, indices: jax.Array,
           n: int, m: int, *, block: Tuple[int, int, int] | None = None,
           interpret: bool | None = None, packed: bool = False) -> jax.Array:
    """Y = X @ W_sp.T for arbitrary leading dims on X.

    packed=True feeds the kernel the paper's bit-packed index stream
    (uint32 words, ceil(log2 M) bits per index) and unpacks in VMEM."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    o = values.shape[0]
    xf = x.reshape(-1, k)
    bsz = xf.shape[0]
    bm, bn, bk = block or pick_block_mm(bsz, o, k, n, m)
    vals, idx, op, kp = _padded_operands(values, indices, bn, k, bk, n, m)
    xf = _pad_axis(_pad_axis(xf, 0, _round_up(bsz, bm)), 1, kp)
    if packed:
        from repro.core.sparsity import pack_indices
        bits = max(1, int(np.ceil(np.log2(m))))
        per_word = 32 // bits
        if (bk // m) % per_word:
            raise ValueError(f"bk//m={bk // m} not a multiple of {per_word}")
        # pack per (slot, K-step) run so every kernel tile starts word-aligned
        idx = pack_indices(
            idx.reshape(op, n * (kp // bk), bk // m), m).reshape(op, -1)
    y = _spmm.nm_xwt_kernel(
        xf, vals, idx, n, m, block=(bm, bn, bk), out_dtype=x.dtype,
        interpret=pallas_interpret() if interpret is None else interpret,
        packed=packed)
    return y[:bsz, :o].reshape(*lead, o)


@functools.partial(jax.jit, static_argnames=("n", "m", "block", "interpret"))
def nm_spmm(values: jax.Array, indices: jax.Array, b: jax.Array,
            n: int, m: int, *, block: Tuple[int, int, int] | None = None,
            interpret: bool | None = None) -> jax.Array:
    """Paper orientation C = A_sp @ B, A compressed [R, K//M*N], B [K, C]."""
    r = values.shape[0]
    k, c = b.shape
    bm, bn, bk = block or pick_block_mm(r, c, k, n, m)
    vals, idx, rp, kp = _padded_operands(values, indices, bm, k, bk, n, m)
    bp = _pad_axis(_pad_axis(b, 0, kp), 1, _round_up(c, bn))
    out = _spmm.nm_spmm_kernel(
        vals, idx, bp, n, m, block=(bm, bn, bk), out_dtype=b.dtype,
        interpret=pallas_interpret() if interpret is None else interpret)
    return out[:r, :c]


@functools.partial(jax.jit, static_argnames=("n", "m", "block", "interpret"))
def nm_spmv(x: jax.Array, values: jax.Array, indices: jax.Array,
            n: int, m: int, *, block: Tuple[int, int] | None = None,
            interpret: bool | None = None) -> jax.Array:
    """Decode-regime Y = X @ W_sp.T with small batch X [..., K]."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    o = values.shape[0]
    xf = x.reshape(-1, k)
    bsz = xf.shape[0]
    bo, bk = block or pick_block_spmv(bsz, o, k, n, m)
    vals, idx, op, kp = _padded_operands(values, indices, bo, k, bk, n, m)
    xf = _pad_axis(_pad_axis(xf, 0, _round_up(bsz, 8)), 1, kp)
    y = _spmv.nm_spmv_kernel(
        xf, vals, idx, n, m, block=(bo, bk), out_dtype=x.dtype,
        interpret=pallas_interpret() if interpret is None else interpret)
    return y[:bsz, :o].reshape(*lead, o)


# ---------------------------------------------------------------------------
# Analytic kernel traffic model (used by launch/roofline.py and the Fig 12
# benchmark).  Counts HBM<->VMEM bytes implied by the BlockSpecs and the MXU/
# VPU flops of the kernel body.  Index bytes use the packed 2-bit format the
# storage layer defines (sparsity.storage_bytes), matching the paper's format.
# ---------------------------------------------------------------------------

def traffic_mm(bsz: int, o: int, k: int, n: int, m: int, *,
               dtype_bytes: int = 2,
               block: Tuple[int, int, int] | None = None,
               sparse: bool = True) -> dict:
    """HBM bytes + flops for Y = X @ W.T (nm_xwt grid: i, j, kk)."""
    bm, bn, bk = block or pick_block_mm(bsz, o, k, n, m)
    bp, op, kp = _round_up(bsz, bm), _round_up(o, bn), _round_up(k, bk)
    j_steps = op // bn
    i_steps = bp // bm
    x_bytes = j_steps * bp * kp * dtype_bytes           # x re-streamed per j
    if sparse:
        idx_bits = max(1, int(np.ceil(np.log2(m))))
        w_elem_bytes = (n / m) * (dtype_bytes + idx_bits / 8)
    else:
        w_elem_bytes = dtype_bytes
    w_bytes = i_steps * op * kp * w_elem_bytes          # w re-streamed per i
    out_bytes = bp * op * dtype_bytes
    mxu_flops = 2.0 * bp * op * kp
    vpu_flops = (2.0 * n / m) * bp * 0 + (3.0 * n) * (op * kp) * i_steps if sparse else 0.0
    return dict(hbm_bytes=x_bytes + w_bytes + out_bytes,
                w_bytes=w_bytes, x_bytes=x_bytes, out_bytes=out_bytes,
                mxu_flops=mxu_flops, vpu_flops=vpu_flops)


def traffic_spmv(bsz: int, o: int, k: int, n: int, m: int, *,
                 dtype_bytes: int = 2,
                 block: Tuple[int, int] | None = None,
                 sparse: bool = True) -> dict:
    """HBM bytes + flops for the decode kernel (x resident, W streamed once)."""
    bo, bk = block or pick_block_spmv(bsz, o, k, n, m)
    bp = _round_up(bsz, 8)
    op, kp = _round_up(o, bo), _round_up(k, bk)
    x_bytes = (op // bo) * bp * kp * dtype_bytes if op > bo else bp * kp * dtype_bytes
    if sparse:
        idx_bits = max(1, int(np.ceil(np.log2(m))))
        w_elem_bytes = (n / m) * (dtype_bytes + idx_bits / 8)
    else:
        w_elem_bytes = dtype_bytes
    w_bytes = op * kp * w_elem_bytes                    # streamed exactly once
    out_bytes = bp * op * dtype_bytes
    return dict(hbm_bytes=x_bytes + w_bytes + out_bytes,
                w_bytes=w_bytes, x_bytes=x_bytes, out_bytes=out_bytes,
                mxu_flops=2.0 * bp * op * kp,
                vpu_flops=(2.0 * n) * op * kp if sparse else 0.0)
