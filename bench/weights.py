"""Seeded dense weights, made by the benchmark and shared by the program
under test and the plain reference.  A family (``bench/families``) lays out
its layers from ``tensor``; the embedding is made here.

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

import jax
import jax.numpy as jnp

# four bytes summed: variance 4 * (256**2 - 1) / 12
_IH_STD = math.sqrt(4 * (256 ** 2 - 1) / 12)

# Role ids: the embedding's is 0, and each family numbers its own roles
# (``ROLES`` in ``bench/families/<family>.py``) above every id in use, so
# adding a family never changes a weight another family is made of.
EMBEDDING = 0


def _normalish(key, shape) -> jax.Array:
    bits = jax.random.bits(key, shape, jnp.uint32)
    s = sum(((bits >> (8 * i)) & 0xFF).astype(jnp.int32) for i in range(4))
    return (s - 510).astype(jnp.float32)


def tensor(key, role: int, layer, shape, scale: float,
           dtype=jnp.bfloat16) -> jax.Array:
    k = jax.random.fold_in(jax.random.fold_in(key, role), layer)
    return (_normalish(k, shape) * jnp.float32(scale / _IH_STD)).astype(dtype)


def embedding(key, model: dict) -> jax.Array:
    v, d = model["vocab_size"], model["hidden_size"]
    return tensor(key, EMBEDDING, 0, (v, d), d ** -0.5)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's exceed 32 bits)."""
    return jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
