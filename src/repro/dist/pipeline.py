"""Pipeline parallelism: GPipe schedule over a mesh axis.

Stage s lives on device s (the stacked per-stage params are sharded over the
pipeline axis); microbatches stream through a collective-permute ring.  At
tick t device s processes microbatch t-s, so the pipeline fills in S-1 ticks
and drains in S-1 ticks — bubble fraction (S-1)/(M+S-1).

All activation traffic is neighbor-to-neighbor ppermute; there is no
all-gather of activations or parameters.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params, x: jax.Array, mesh,
                   axis: str = "pp") -> jax.Array:
    """Apply n_stages sequential stages to M microbatches, pipelined.

    stage_fn(stage_params, xb) -> yb must preserve xb's shape (stages chain).
    params: pytree whose leaves are stacked [n_stages, ...] (stage s uses
    leaf[s]); x: [M, ...microbatch...].  Returns [M, ...] — the composition
    stage_{S-1}( ... stage_0(x) ... ) per microbatch, replicated.
    """
    n = mesh.shape[axis]
    n_stages = jax.tree.leaves(params)[0].shape[0]
    if n_stages != n:
        raise ValueError(f"{n_stages} stages need a {axis}-axis of the same "
                         f"size, mesh has {n}")
    num_mb = x.shape[0]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def shard(w, xloc):
        w = jax.tree.map(lambda a: a[0], w)    # this device's stage params
        s = lax.axis_index(axis)
        carry = jnp.zeros(xloc.shape[1:], xloc.dtype)
        outs = jnp.zeros_like(xloc)
        for t in range(num_mb + n - 1):
            # device 0 injects microbatch t; everyone else consumes the ring
            x_t = xloc[t] if t < num_mb else jnp.zeros_like(carry)
            inp = jnp.where(s == 0, x_t, carry)
            out = stage_fn(w, inp)
            slot = t - (n - 1)                 # microbatch the LAST stage
            if slot >= 0:                      # just finished (static index)
                outs = outs.at[slot].set(out)
            carry = lax.ppermute(out, axis, perm)
        # only the last device's outs are finished work; replicate via psum
        return lax.psum(jnp.where(s == n - 1, outs, jnp.zeros_like(outs)),
                        axis)

    return jax.shard_map(shard, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P())(params, x)
