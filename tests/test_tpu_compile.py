"""The serving path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers one kernel for a *described* v5e chip (the
TPU compiler is installed, no chip is attached) and checks that Mosaic
accepted it (``tpu_custom_call`` in the compiled HLO).  This catches what
interpret mode cannot — unaligned tiles, unsupported in-kernel reshapes or
gathers, block shapes that break the (8, 128) rule — at no chip time.

Widths: llama3.2-1b decode and prefill (K 2048, O 8192, d_ff 8192, batch 8,
block size 8, KVH 8, G 4, d 64) in bf16, the benchmark's paged GQA decode
at deepseek-67b's width (64 slots, KVH 8, G 8, d 128, a table of 80 blocks
of 16, a pool of 3072), and deepseek-v2-lite's MLA decode (H 16, kv_lora
512, rope 64).  The topology is described inside a fixture,
never at import: only one process may hold the TPU library, and every test
worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sparsity import compress
from repro.kernels import ops as kops
from repro.kernels.flash_attention import (paged_gqa_decode, paged_gqa_verify,
                                           paged_mla_decode, paged_mla_verify)

B, K, O, FF = 8, 2048, 8192, 8192          # decode batch, d_model, widths
PREFILL = 384                               # a prefill bucket
BS, KVH, G, D = 8, 8, 4, 64                 # paged GQA pool
T = 68                                      # table width: 544 positions
H, R, RD = 16, 512, 64                      # MLA (deepseek-v2-lite)
SPAN = 4                                    # speculative verify: k + 1


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,m,o,k", [
    (2, 4, O, K),          # q/k/v/gate/up shapes at 2:4
    (2, 4, K, FF),         # down projection
    (1, 4, O, K),          # the speculative draft's re-ranked 1:4 view
])
def test_nm_spmv_decode(one_chip, n, m, o, k):
    nnz = k // m * n
    _assert_kernel(lambda x, v, i: kops.nm_spmv(x, v, i, n, m,
                                                interpret=False),
                   _sds(one_chip, (B, k), jnp.bfloat16),
                   _sds(one_chip, (o, nnz), jnp.bfloat16),
                   _sds(one_chip, (o, nnz), jnp.int8))


@pytest.mark.parametrize("o,k", [(O, K), (K, FF)])
def test_nm_xwt_prefill_tile(one_chip, o, k):
    nnz = k // 2
    _assert_kernel(lambda x, v, i: kops.nm_xwt(x, v, i, 2, 4,
                                               interpret=False),
                   _sds(one_chip, (PREFILL, k), jnp.bfloat16),
                   _sds(one_chip, (o, nnz), jnp.bfloat16),
                   _sds(one_chip, (o, nnz), jnp.int8))


def test_nm_spmm(one_chip):
    _assert_kernel(lambda v, i, b: kops.nm_spmm(v, i, b, 2, 4,
                                                interpret=False),
                   _sds(one_chip, (K, K // 2), jnp.bfloat16),
                   _sds(one_chip, (K, K // 2), jnp.int8),
                   _sds(one_chip, (K, PREFILL), jnp.bfloat16))


def test_compress_stacked_weights(one_chip):
    """The offline packing of a stacked [L, d_ff, d] weight fits the chip:
    a [.., NB, M] or [.., NB, N] intermediate pads its minor axis to 128
    lanes on a TPU, which took 34 GB for this weight."""
    w = _sds(one_chip, (16, FF, K), jnp.bfloat16)
    compiled = jax.jit(lambda w: compress(w, 2, 4)).lower(w).compile()
    w_bytes = 16 * FF * K * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * w_bytes


def _gqa_args(sharding, span=(), b=B, kvh=KVH, g=G, d=D, bs=BS, t=T,
              blocks=B * T + 1):
    pool = _sds(sharding, (blocks, bs, kvh, d), jnp.bfloat16)
    return (_sds(sharding, (b,) + span + (kvh, g, d), jnp.bfloat16), pool,
            pool, _sds(sharding, (b, t), jnp.int32),
            _sds(sharding, (b,), jnp.int32))


def test_paged_gqa_decode(one_chip):
    _assert_kernel(lambda *a: paged_gqa_decode(*a, scale=D ** -0.5),
                   *_gqa_args(one_chip))


def test_paged_gqa_decode_at_benchmark_width(one_chip):
    _assert_kernel(lambda *a: paged_gqa_decode(*a, scale=128 ** -0.5),
                   *_gqa_args(one_chip, b=64, kvh=8, g=8, d=128, bs=16, t=80,
                              blocks=3072))


def test_paged_gqa_decode_windowed_softcapped(one_chip):
    _assert_kernel(lambda *a: paged_gqa_decode(*a, window=256, cap=50.0),
                   *_gqa_args(one_chip))


def test_paged_gqa_verify(one_chip):
    _assert_kernel(lambda *a: paged_gqa_verify(*a, scale=D ** -0.5),
                   *_gqa_args(one_chip, span=(SPAN,)))


def _mla_args(sharding, lead):
    return (_sds(sharding, lead + (H, R), jnp.float32),
            _sds(sharding, lead + (H, RD), jnp.float32),
            _sds(sharding, (B * T + 1, BS, R), jnp.bfloat16),
            _sds(sharding, (B * T + 1, BS, RD), jnp.bfloat16),
            _sds(sharding, (B, T), jnp.int32),
            _sds(sharding, (B,), jnp.int32))


def test_paged_mla_decode(one_chip):
    _assert_kernel(lambda *a: paged_mla_decode(*a, scale=0.07),
                   *_mla_args(one_chip, (B,)))


def test_paged_mla_verify(one_chip):
    _assert_kernel(lambda *a: paged_mla_verify(*a, scale=0.07),
                   *_mla_args(one_chip, (B, SPAN)))
