"""Plain reference of the served model, independent of the program.

The model's family (``bench/families``) gives the arithmetic of a layer
and of the LM head, in float32 ``jax.numpy`` at "highest" matmul precision
(``lin``, ``rms``); this module runs it layer by layer.  Every linear is
pruned 2:4 by magnitude along its input axis (the two largest of each four
consecutive weights, ties to the lower index), which is the served model's
arithmetic.  Weights come from ``bench.weights``, regenerated one layer at
a time from the seed, so the whole model is never resident.

The controls put a lower precision in the program's place: every pruned
linear and the embedding quantised with one scale per output row, to int8
(``precision="int8"``, symmetric) or to float8 e4m3 (``"fp8"``): the step
below the configuration's bfloat16 that a faster path might take.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench import families, weights

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 1024          # sequences are padded to a multiple of this length
Q_CHUNK = 512       # attention queries per step


def prune_2_4(w: jax.Array, n: int = 2, m: int = 4) -> jax.Array:
    """Keep the ``n`` largest magnitudes of each ``m`` consecutive entries
    along the last axis, ties to the lower index."""
    planes = [w[..., c::m] for c in range(m)]
    mags = [jnp.abs(p.astype(jnp.float32)) for p in planes]
    kept = []
    for i in range(m):
        ahead = sum(((mags[j] >= mags[i]) if j < i else (mags[j] > mags[i]))
                    .astype(jnp.int32) for j in range(m) if j != i)
        kept.append(jnp.where(ahead < n, planes[i], jnp.zeros_like(planes[i])))
    return jnp.stack(kept, axis=-1).reshape(w.shape)


def quantize_int8(w: jax.Array):
    """Symmetric int8 with one scale per row: ``w ~ q * scale``."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-1, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_fp8(w: jax.Array):
    """float8 e4m3 with one scale per row: ``w ~ q * scale``."""
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=-1, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (wf / scale).astype(jnp.float8_e4m3fn), scale


QUANTIZE = {"int8": quantize_int8, "fp8": quantize_fp8}


def _mat(w) -> jax.Array:
    if isinstance(w, tuple):
        q, scale = w
        return q.astype(jnp.float32) * scale
    return w.astype(jnp.float32)


def lin(x, w):
    """``x @ w.T`` in float32, for a plain or a quantised ``w``."""
    return jnp.dot(x, _mat(w).T, precision=HIGHEST)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


class Reference:
    """``logits(seqs, rows)``: for each token sequence, the reference's
    float32 logits at the requested positions, left on the device."""

    def __init__(self, model: dict, seed: int, precision: str = "f32"):
        if precision != "f32" and precision not in QUANTIZE:
            raise ValueError(f"precision must be f32, int8 or fp8: "
                             f"{precision!r}")
        self.model = model
        self.family = families.family(model)
        self.key = weights.seed_key(seed)
        self.precision = precision
        sp = model["sparsity"]
        self.n, self.m = sp["n"], sp["m"]

    @functools.partial(jax.jit, static_argnums=0)
    def _layer(self, key, index):
        out = {}
        for name, w in self.family.layer(key, self.model, index).items():
            w = prune_2_4(w, self.n, self.m)
            out[name] = (QUANTIZE[self.precision](w)
                         if self.precision != "f32" else w)
        return out

    @functools.partial(jax.jit, static_argnums=0)
    def _embedding(self, key):
        e = weights.embedding(key, self.model)
        return QUANTIZE[self.precision](e) if self.precision != "f32" else e

    @functools.partial(jax.jit, static_argnums=0)
    def _block(self, w, h):
        return self.family.block(self.model, w, h)

    @functools.partial(jax.jit, static_argnums=0)
    def _head(self, emb, h, rows):
        return self.family.head(self.model, emb, h[rows])

    def logits(self, seqs: Sequence[np.ndarray],
               rows: Sequence[np.ndarray]) -> List[np.ndarray]:
        emb = self._embedding(self.key)
        hs = []
        for s in seqs:
            t = -(-len(s) // PAD) * PAD
            ids = np.zeros(t, np.int32)
            ids[:len(s)] = s
            hs.append(_mat(emb)[jnp.asarray(ids)] if isinstance(emb, tuple)
                      else emb[jnp.asarray(ids)].astype(jnp.float32))
        for i in range(self.model["num_hidden_layers"]):
            w = self._layer(self.key, jnp.int32(i))
            hs = [self._block(w, h) for h in hs]
            del w
        out = []
        for h, r in zip(hs, rows):
            idx = np.zeros(-(-len(r) // PAD) * PAD, np.int32)
            idx[:len(r)] = r
            out.append(self._head(emb, h, jnp.asarray(idx))[:len(r)])
        return out


def served_gaps(ref_logits, tokens) -> np.ndarray:
    """Per position: how far the served token's reference logit lies below
    the reference's best."""
    ref = jnp.asarray(ref_logits)
    tok = jnp.asarray(tokens, jnp.int32)
    best = jnp.max(ref, axis=-1)
    return np.asarray(best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0])


def control_gaps(ref_logits, ctl_logits) -> np.ndarray:
    """Per position: the gap of the token that the control puts first."""
    return served_gaps(ref_logits, jnp.argmax(jnp.asarray(ctl_logits), -1))
