"""Seeded dense weights of a llama-architecture decoder, made by the
benchmark and shared by the program under test and the plain reference.

Every tensor is a function of (seed, role, layer) only, computed with
integer arithmetic and one rounding multiply, so the same call gives the
same bits inside any jitted program: the program's initialiser and the
reference, which regenerates one layer at a time, see identical weights.

Values are an Irwin-Hall sum of four random bytes (mean 0, close to normal)
scaled to a standard deviation of ``in_dim ** -0.5``; the embedding, which
is also the LM head, uses ``d_model ** -0.5``.  Norm scales are ones.
Matrices are stored ``[out, in]``: ``y = x @ w.T``.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

# four bytes summed: variance 4 * (256**2 - 1) / 12
_IH_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)

LINEARS = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
_ROLE_ID = {name: i for i, name in enumerate(("emb",) + LINEARS)}


def shapes(model: dict) -> Dict[str, Tuple[int, int]]:
    """``[out, in]`` of each linear of one layer, from the configuration."""
    d = model["hidden_size"]
    hd = model["head_dim"]
    h, kv = model["num_attention_heads"], model["num_key_value_heads"]
    f = model["intermediate_size"]
    return {"wq": (h * hd, d), "wk": (kv * hd, d), "wv": (kv * hd, d),
            "wo": (d, h * hd), "wg": (f, d), "wu": (f, d), "wd": (d, f)}


def _normalish(key, shape) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint32)
    s = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4))
    return (s - 510).astype(jnp.float32)


def tensor(key, role: str, layer, shape, scale: float,
           dtype=jnp.bfloat16) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, _ROLE_ID[role]), layer)
    return (_normalish(k, shape) * jnp.float32(scale / _IH_STD)).astype(dtype)


def embedding(key, model: dict) -> jax.Array:
    v, d = model["vocab_size"], model["hidden_size"]
    return tensor(key, "emb", 0, (v, d), d ** -0.5)


def layer(key, model: dict, index) -> Dict[str, jax.Array]:
    """The dense bf16 linears of layer ``index`` (may be traced)."""
    return {name: tensor(key, name, index, shp, shp[1] ** -0.5)
            for name, shp in shapes(model).items()}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's exceed 32 bits)."""
    return jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
