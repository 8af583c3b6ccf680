"""CNN-as-GEMM: sparse conv vs lax.conv with sparsified dense weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import decompress
from repro.models.cnn import (CNN_LAYER_GEMMS, conv2d_sparse, im2col,
                              sparse_conv_init)


@pytest.mark.parametrize("stride,pad", [(1, "SAME"), (2, "SAME"), (1, "VALID")])
def test_conv2d_sparse_matches_dense_conv(stride, pad):
    c_in, c_out, kh, kw = 8, 16, 3, 3
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 12, c_in))
    sp = sparse_conv_init(jax.random.PRNGKey(1), c_in, c_out, kh, kw, 2, 4)
    y = conv2d_sparse(x, sp, kh, kw, stride, pad)
    # dense reference with the decompressed weights; im2col features are in
    # (C, KH, KW) order (conv_general_dilated_patches convention)
    w_dense = decompress(sp)                       # [c_out, c_in*kh*kw]
    w_hwio = w_dense.reshape(c_out, c_in, kh, kw).transpose(2, 3, 1, 0)
    y_ref = jax.lax.conv_general_dilated(
        x, w_hwio, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_conv2d_sparse_pallas_interpret():
    c_in, c_out = 8, 16
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 8, c_in))
    sp = sparse_conv_init(jax.random.PRNGKey(3), c_in, c_out, 3, 3, 1, 4)
    y_xla = conv2d_sparse(x, sp, 3, 3, impl="xla")
    y_pl = conv2d_sparse(x, sp, 3, 3, impl="pallas")
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_xla),
                               rtol=2e-4, atol=2e-4)


def test_im2col_shapes():
    x = jnp.ones((2, 14, 14, 8))
    cols, (ho, wo) = im2col(x, 3, 3, stride=2, padding="SAME")
    assert (ho, wo) == (7, 7)
    assert cols.shape == (2 * 49, 8 * 9)


def test_layer_tables_complete():
    assert set(CNN_LAYER_GEMMS) == {"resnet50", "densenet121", "inceptionv3"}
    for net, layers in CNN_LAYER_GEMMS.items():
        assert len(layers) >= 5
        for (name, r, k, spatial) in layers:
            assert r > 0 and k > 0 and spatial > 0
