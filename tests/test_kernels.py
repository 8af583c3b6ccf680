"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp ref oracle,
executed with interpret=True on CPU (TPU is the lowering target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import compress
from repro.kernels import ops as kops
from repro.kernels import ref as kref

SHAPES = [  # (batch/rows, out, k)
    (8, 16, 32),            # tiny, unaligned with default blocks
    (64, 128, 256),
    (130, 96, 520),         # deliberately ragged -> padding paths
    (256, 256, 512),
]
NM = [(1, 4), (2, 4), (1, 2)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nm", NM)
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_nm_xwt_kernel_matches_ref(nm, shape, dtype):
    n, m = nm
    b, o, k = shape
    k = -(-k // m) * m
    kw = jax.random.split(jax.random.PRNGKey(0))
    w = jax.random.normal(kw[0], (o, k), jnp.float32).astype(dtype)
    x = jax.random.normal(kw[1], (b, k), jnp.float32).astype(dtype)
    sp = compress(w, n, m)
    y = kops.nm_xwt(x, sp.values, sp.indices, n, m, interpret=True)
    y_ref = kref.nm_xwt_ref(x.astype(jnp.float32),
                            sp.values.astype(jnp.float32), sp.indices, n, m)
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(y_ref), **_tol(dtype))


@pytest.mark.parametrize("nm", NM)
@pytest.mark.parametrize("shape", SHAPES[:3])
def test_nm_spmm_kernel_matches_ref(nm, shape):
    n, m = nm
    r, c, k = shape
    k = -(-k // m) * m
    kw = jax.random.split(jax.random.PRNGKey(1))
    a = jax.random.normal(kw[0], (r, k), jnp.float32)
    b = jax.random.normal(kw[1], (k, c), jnp.float32)
    sp = compress(a, n, m)
    y = kops.nm_spmm(sp.values, sp.indices, b, n, m, interpret=True)
    y_ref = kref.nm_spmm_ref(sp.values, sp.indices, b, n, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nm", NM)
@pytest.mark.parametrize("b", [4, 8])
def test_nm_spmv_kernel_matches_ref(nm, b):
    n, m = nm
    o, k = 192, 512
    kw = jax.random.split(jax.random.PRNGKey(2))
    w = jax.random.normal(kw[0], (o, k), jnp.float32)
    x = jax.random.normal(kw[1], (b, k), jnp.float32)
    sp = compress(w, n, m)
    y = kops.nm_spmv(x, sp.values, sp.indices, n, m, interpret=True)
    y_ref = kref.nm_spmv_ref(x, sp.values, sp.indices, n, m)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_kernel_custom_blocks():
    n, m = 2, 4
    w = jax.random.normal(jax.random.PRNGKey(3), (128, 256))
    x = jax.random.normal(jax.random.PRNGKey(4), (32, 256))
    sp = compress(w, n, m)
    y_ref = kref.nm_xwt_ref(x, sp.values, sp.indices, n, m)
    for block in [(16, 64, 128), (32, 128, 256), (8, 128, 64)]:
        y = kops.nm_xwt(x, sp.values, sp.indices, n, m, block=block,
                        interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)


def test_leading_dims_flattened():
    n, m = 2, 4
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 128))
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 128))
    sp = compress(w, n, m)
    y = kops.nm_xwt(x, sp.values, sp.indices, n, m, interpret=True)
    assert y.shape == (2, 3, 64)
    y_ref = kref.nm_xwt_ref(x.reshape(-1, 128), sp.values, sp.indices, n, m)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 64)),
                               np.asarray(y_ref), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("nm", NM)
def test_packed_index_kernel_matches_ref(nm):
    """The paper's bit-packed col_idx stream consumed directly by the kernel
    (unpack-in-VMEM): must agree with the int8-index path and the oracle."""
    n, m = nm
    w = jax.random.normal(jax.random.PRNGKey(7), (192, 512))
    x = jax.random.normal(jax.random.PRNGKey(8), (24, 512))
    sp = compress(w, n, m)
    y_ref = kref.nm_xwt_ref(x, sp.values, sp.indices, n, m)
    y_pk = kops.nm_xwt(x, sp.values, sp.indices, n, m, interpret=True,
                       packed=True)
    np.testing.assert_allclose(np.asarray(y_pk), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_unpack_indices_tile_roundtrip_2bit_words():
    """M=4 -> 2-bit indices, 16 per uint32 word: the in-VMEM unpack must be
    the exact inverse of the storage layer's pack_indices."""
    from repro.core.sparsity import pack_indices
    from repro.kernels.nm_spmm import _unpack_indices_tile
    n, m = 2, 4
    w = jax.random.normal(jax.random.PRNGKey(9), (16, 128))
    sp = compress(w, n, m)                    # nnz = 64 = 4 full words/row
    pk = pack_indices(sp.indices, m)
    out = _unpack_indices_tile(pk, n, m, sp.nnz_per_row)
    assert out.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(sp.indices, np.int32))


def test_unpack_indices_tile_roundtrip_3bit_words():
    """M=8 -> 3-bit indices, 10 per word: a non-power-of-two slot count
    exercises the slot%per_word addressing, including a ragged final word."""
    from repro.core.sparsity import pack_indices
    from repro.kernels.nm_spmm import _unpack_indices_tile
    n, m = 2, 8
    for k in (80, 64):                        # nnz = 20 (full) / 16 (ragged)
        w = jax.random.normal(jax.random.PRNGKey(10), (12, k))
        sp = compress(w, n, m)
        pk = pack_indices(sp.indices, m)
        out = _unpack_indices_tile(pk, n, m, sp.nnz_per_row)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(sp.indices, np.int32))


def test_packed_rejects_unaligned_block():
    """bk whose per-block nnz is not a whole number of packed words must be
    rejected up front (the kernel tile could not start word-aligned)."""
    n, m = 2, 4                               # 2-bit indices, 16 per word
    w = jax.random.normal(jax.random.PRNGKey(11), (32, 48))
    x = jax.random.normal(jax.random.PRNGKey(12), (8, 48))
    sp = compress(w, n, m)
    with pytest.raises(ValueError, match="not a multiple"):
        kops.nm_xwt(x, sp.values, sp.indices, n, m, block=(8, 32, 24),
                    interpret=True, packed=True)   # bnnz = 12, per_word = 16


# ---------------------------------------------------------------------------
# Differential net: every kernel orientation vs the jnp oracle, across the
# paper's N:M patterns, both index streams (int8 and the bit-packed col_idx
# words), and bf16/f32 inputs.  nm_xwt consumes packed words natively
# (unpack-in-VMEM); nm_spmm/nm_spmv take int8, so their packed coverage
# round-trips the index stream through the storage format first — the kernel
# then multiplies exactly what the packed words decode to.
# ---------------------------------------------------------------------------

DIFF_NM = [(1, 4), (2, 4), (2, 8)]
DIFF_DTYPES = [jnp.float32, jnp.bfloat16]


def _storage_roundtrip(indices, m, nnz):
    from repro.core.sparsity import pack_indices, unpack_indices
    return unpack_indices(pack_indices(indices, m), m, nnz)


def _diff_problem(n, m, o, k, b, dtype, seed):
    kw = jax.random.split(jax.random.PRNGKey(seed), 2)
    w = jax.random.normal(kw[0], (o, k), jnp.float32).astype(dtype)
    x = jax.random.normal(kw[1], (b, k), jnp.float32).astype(dtype)
    return x, compress(w, n, m)


@pytest.mark.parametrize("nm", DIFF_NM)
@pytest.mark.parametrize("dtype", DIFF_DTYPES)
@pytest.mark.parametrize("packed", [False, True])
def test_diff_xwt_kernel(nm, dtype, packed):
    n, m = nm
    # m=8 -> 3-bit indices, 10/word: bk=80 keeps every tile word-aligned and
    # k=160 forces a multi-k-step accumulation through the packed path.
    o, k, b = (64, 160, 16) if m == 8 else (96, 256, 16)
    block = (8, 64, 80) if m == 8 else None
    x, sp = _diff_problem(n, m, o, k, b, dtype, seed=21)
    y = kops.nm_xwt(x, sp.values, sp.indices, n, m, block=block,
                    interpret=True, packed=packed)
    y_ref = kref.nm_xwt_ref(x.astype(jnp.float32),
                            sp.values.astype(jnp.float32), sp.indices, n, m)
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(y_ref), **_tol(dtype))


@pytest.mark.parametrize("nm", DIFF_NM)
@pytest.mark.parametrize("dtype", DIFF_DTYPES)
@pytest.mark.parametrize("idx_stream", ["int8", "packed_roundtrip"])
def test_diff_spmm_kernel(nm, dtype, idx_stream):
    n, m = nm
    r, c, k = 48, 96, 160 if m == 8 else 192
    kw = jax.random.split(jax.random.PRNGKey(23), 2)
    a = jax.random.normal(kw[0], (r, k), jnp.float32).astype(dtype)
    b = jax.random.normal(kw[1], (k, c), jnp.float32).astype(dtype)
    sp = compress(a, n, m)
    idx = sp.indices if idx_stream == "int8" else \
        _storage_roundtrip(sp.indices, m, sp.nnz_per_row)
    y = kops.nm_spmm(sp.values, idx, b, n, m, interpret=True)
    y_ref = kref.nm_spmm_ref(sp.values.astype(jnp.float32), idx,
                             b.astype(jnp.float32), n, m)
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(y_ref, jnp.float32), **_tol(dtype))


@pytest.mark.parametrize("nm", DIFF_NM)
@pytest.mark.parametrize("dtype", DIFF_DTYPES)
@pytest.mark.parametrize("idx_stream", ["int8", "packed_roundtrip"])
def test_diff_spmv_kernel(nm, dtype, idx_stream):
    n, m = nm
    o, k, b = 64, 160 if m == 8 else 256, 4
    x, sp = _diff_problem(n, m, o, k, b, dtype, seed=25)
    idx = sp.indices if idx_stream == "int8" else \
        _storage_roundtrip(sp.indices, m, sp.nnz_per_row)
    y = kops.nm_spmv(x, sp.values, idx, n, m, interpret=True)
    y_ref = kref.nm_spmv_ref(x.astype(jnp.float32),
                             sp.values.astype(jnp.float32), idx, n, m)
    np.testing.assert_allclose(np.asarray(y, jnp.float32),
                               np.asarray(y_ref, jnp.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# Decode-shape execution policy (PR 3): with impl='auto', compressed linears
# route by input shape — seq-len-1 decode steps and rank-2 small-batch
# matvecs take the nm_spmv vindexmac path, prefill shapes keep the nm_spmm
# tile path.  The policy lives in one place (sparse_matmul.select_impl), so
# these are its unit tests.
# ---------------------------------------------------------------------------

import dataclasses

from repro.core.sparse_matmul import (SparsityConfig, default_impl,
                                      is_decode_shape, nm_matmul, select_impl)
from repro.core.sparsity import NMSparse


def test_decode_shape_detection():
    assert is_decode_shape((4, 1, 256))          # [B, 1, d] decode step
    assert not is_decode_shape((4, 32, 256))     # prefill / training
    assert is_decode_shape((8, 256))             # small-batch matvec
    assert not is_decode_shape((64, 256))        # GEMM-sized batch
    assert is_decode_shape((2, 4, 1, 256))       # any leading dims, seq 1


def test_select_impl_cpu_routes_decode_to_fused_decompress():
    cfg = SparsityConfig(impl="auto")
    # non-TPU backends: decode keeps the fused slot-loop decompress (same
    # decompress order as the kernel, bitwise-stable vs the masked path)
    assert select_impl(cfg, (4, 1, 256)) == "xla"
    assert select_impl(cfg, (4, 32, 256)) == default_impl((4, 32, 256))
    # an explicitly pinned impl always wins over the shape policy
    assert select_impl(dataclasses.replace(cfg, impl="ref"), (4, 1, 256)) == "ref"
    # decode_impl pins only the decode side
    pin = dataclasses.replace(cfg, decode_impl="spmv")
    assert select_impl(pin, (4, 1, 256)) == "spmv"
    assert select_impl(pin, (4, 32, 256)) == default_impl((4, 32, 256))


def test_select_impl_tpu_routes_decode_to_spmv(monkeypatch):
    import repro.core.sparse_matmul as sm
    monkeypatch.setattr(sm.jax, "default_backend", lambda: "tpu")
    cfg = SparsityConfig(impl="auto")
    assert select_impl(cfg, (4, 1, 256)) == "spmv"       # seq-len 1: vindexmac
    assert select_impl(cfg, (4, 32, 256)) == "pallas"    # prefill: spmm tiles
    assert select_impl(cfg, (2, 256)) == "spmv"          # small-batch matvec
    # GSPMD cannot partition a Pallas call: under a multi-device mesh both
    # shapes take the XLA formulation
    from jax.sharding import AbstractMesh
    from repro.dist.api import SERVE_TP_RULES, axis_rules
    with axis_rules(AbstractMesh((4,), ("model",)), SERVE_TP_RULES):
        assert select_impl(cfg, (4, 1, 256)) == "xla"
        assert select_impl(cfg, (4, 32, 256)) == "xla"


@pytest.mark.parametrize("impl", ["spmv"])
def test_nm_matmul_spmv_impl_matches_ref(impl):
    """The spmv impl names dispatch through nm_matmul to the decode kernel
    (leading dims flattened like every other route)."""
    n, m = 2, 4
    kw = jax.random.split(jax.random.PRNGKey(30))
    w = jax.random.normal(kw[0], (64, 256))
    x = jax.random.normal(kw[1], (4, 1, 256))
    sp = compress(w, n, m)
    y = nm_matmul(x, sp, impl=impl)
    assert y.shape == (4, 1, 64)
    y_ref = kref.nm_spmv_ref(x.reshape(-1, 256), sp.values, sp.indices, n, m)
    np.testing.assert_allclose(np.asarray(y.reshape(-1, 64)),
                               np.asarray(y_ref), rtol=1e-4, atol=1e-4)


def test_decode_route_spmv_matches_tile_path():
    """Full policy stack on one SparseLinear: a decode-shaped input forced
    through the spmv kernel (interpret mode) agrees with the xla tile-path
    result for the same compressed params."""
    from repro.core.layers import linear_apply, linear_init
    cfg = SparsityConfig(n=2, m=4, impl="auto", decode_impl="spmv",
                         min_dim=64)
    p = linear_init(jax.random.PRNGKey(31), 256, 128,
                    dataclasses.replace(cfg, mode="compressed"),
                    dtype=jnp.float32)
    assert "w_vals" in p
    x = jax.random.normal(jax.random.PRNGKey(32), (4, 1, 256))
    y_spmv = linear_apply(p, x, cfg)
    y_tile = linear_apply(p, x, dataclasses.replace(cfg, impl="xla"))
    np.testing.assert_allclose(np.asarray(y_spmv), np.asarray(y_tile),
                               rtol=1e-5, atol=1e-5)


def test_traffic_model_sparse_beats_dense():
    from repro.kernels.ops import traffic_mm, traffic_spmv
    s = traffic_mm(512, 1024, 4096, 2, 4, sparse=True)
    d = traffic_mm(512, 1024, 4096, 2, 4, sparse=False)
    assert s["w_bytes"] < d["w_bytes"]
    assert s["x_bytes"] == d["x_bytes"]
    sv = traffic_spmv(8, 1024, 4096, 2, 4, sparse=True)
    dv = traffic_spmv(8, 1024, 4096, 2, 4, sparse=False)
    # decode regime: weight stream dominates; 2:4 cuts it by ~44 %
    assert sv["w_bytes"] / dv["w_bytes"] == pytest.approx(0.5625, rel=1e-3)
