"""CNN layers as sparse-dense GEMM — the paper's own evaluation domain.

Each convolution is lowered to C = A x B exactly as in the paper (§IV):
A = [C_out, C_in*kh*kw] N:M-sparse weights, B = im2col patches
[C_in*kh*kw, H_out*W_out*batch].  The benchmark harness (Fig 11/12) runs the
ResNet50 / DenseNet121 / InceptionV3 layer lists through this path.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparsity import NMSparse, compress
from repro.core.sparse_matmul import nm_matmul


def im2col(x: jax.Array, kh: int, kw: int, stride: int = 1,
           padding: str = "SAME") -> Tuple[jax.Array, Tuple[int, int]]:
    """x [B, H, W, C] -> patches [B*Ho*Wo, C*kh*kw].

    Patch features are ordered (C, KH, KW) — channel slowest — per
    conv_general_dilated_patches; sparse conv weights [C_out, C*kh*kw] use
    the same flat layout."""
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    b, ho, wo, ck = patches.shape
    return patches.reshape(b * ho * wo, ck), (ho, wo)


def conv2d_sparse(x: jax.Array, w_sp: NMSparse, kh: int, kw: int,
                  stride: int = 1, padding: str = "SAME",
                  impl: str = "xla") -> jax.Array:
    """Sparse conv via im2col GEMM.  w_sp dense_shape [C_out, ck_padded]
    where ck_padded = round_up(C_in*kh*kw, M) (stem convs with C_in=3 have
    27 patch features — the weight's reduction axis is zero-padded)."""
    b = x.shape[0]
    cols, (ho, wo) = im2col(x, kh, kw, stride, padding)   # [B*Ho*Wo, CK]
    ckp = w_sp.dense_shape[-1]
    if cols.shape[-1] < ckp:
        cols = jnp.pad(cols, ((0, 0), (0, ckp - cols.shape[-1])))
    y = nm_matmul(cols, w_sp, impl=impl)                  # [B*Ho*Wo, C_out]
    return y.reshape(b, ho, wo, -1)


def sparse_conv_init(key, c_in: int, c_out: int, kh: int, kw: int,
                     n: int, m: int, dtype=jnp.float32) -> NMSparse:
    ck = c_in * kh * kw
    ckp = -(-ck // m) * m                     # pad reduction axis to M blocks
    w = (jax.random.normal(key, (c_out, ck), jnp.float32)
         * ck ** -0.5).astype(dtype)
    if ckp != ck:
        w = jnp.pad(w, ((0, 0), (0, ckp - ck)))
    return compress(w, n, m)


# --- representative im2col GEMM dims (R=C_out, K=C_in*kh*kw, C=Ho*Wo*B) ---
# for the three CNNs the paper evaluates; layer ids follow the paper's
# DenseNet121 examples (layers 5, 23, 87) plus per-net coverage.
# (R, K, spatial) with spatial = Ho*Wo for batch 1.
CNN_LAYER_GEMMS = {
    "densenet121": [
        ("L5", 128, 288, 3136),      # 3x3 conv on 56x56, growth-rate block
        ("L23", 128, 1152, 784),     # deeper dense block, 28x28
        ("L87", 128, 1152, 196),     # 14x14
        ("L1", 64, 147, 12544),      # stem 7x7x3
        ("trans2", 256, 512, 784),   # transition 1x1
    ],
    "resnet50": [
        ("conv2_3x3", 64, 576, 3136),
        ("conv3_3x3", 128, 1152, 784),
        ("conv4_3x3", 256, 2304, 196),
        ("conv5_3x3", 512, 4608, 49),
        ("conv4_1x1", 1024, 256, 196),
    ],
    "inceptionv3": [
        ("mix5_3x3", 64, 432, 1225),
        ("mix6_7x1", 192, 1344, 289),
        ("mix7_3x3", 384, 1152, 64),
        ("stem_3x3", 32, 288, 21609),
        ("mix6_1x1", 192, 768, 289),
    ],
}
