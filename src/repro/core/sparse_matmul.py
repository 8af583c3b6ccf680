"""Sparse matmul dispatch + straight-through training path.

One entry point (``nm_matmul``) with several implementations:

  ref              decompress -> dense einsum (oracle; kernels/ref.py)
  xla              slot-loop decompress fused by XLA -> dense dot.  The CPU /
                   dry-run path: numerically identical to the Pallas kernel
                   (same decompress order, f32 accumulation).
  xla_gather       gather-MAC formulation (Alg 6 semantics) — N/M flops; used
                   for small-batch decode on CPU where XLA executes the real
                   FLOP reduction.
  pallas           tile kernel (kernels/nm_spmm.py)
  spmv             decode kernel (kernels/nm_spmv.py)
  ring             TP serving: explicit sparse ring collective

The Pallas impls run natively on a TPU and in interpret mode elsewhere
(``kernels.ops.pallas_interpret``), so CPU runs validate the kernel bodies.

Training uses ``nm_matmul_ste``: SR-STE (Zhou et al., paper ref [3]) —
the N:M mask is recomputed from the dense weights every step, gradients pass
straight through, and pruned weights receive a decay pull so the mask anneals.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.sparsity import NMSparse, nm_mask, top_planes
from repro.kernels import ops as kops
from repro.kernels import ref as kref

Impl = str  # 'auto' | 'ref' | 'xla' | 'xla_gather' | 'pallas' | 'spmv' | 'ring'


@dataclasses.dataclass(frozen=True)
class SparsityConfig:
    """Per-model sparsity policy (threaded through every SparseLinear)."""
    n: int = 2
    m: int = 4
    enabled: bool = True
    mode: str = "srste"          # 'srste' | 'fixed' | 'compressed' | 'dense'
    impl: Impl = "auto"
    srste_lam: float = 2e-4      # SR-STE decay on pruned weights
    min_dim: int = 128           # skip tiny projections
    # Decode execution policy (PR 3).  With impl='auto', every compressed
    # linear routes by *input shape* instead of per-call plumbing: decode-
    # shaped inputs ([..., 1, K] single-token steps, or rank-2 matvecs with
    # batch <= decode_batch_max) take the nm_spmv vindexmac path (paper
    # Alg 6: weight stream read once, indirect local reads of the resident
    # activations), everything else keeps the nm_spmm tile path.  decode_impl
    # pins the decode-side choice ('auto' resolves per backend: spmv on TPU,
    # the fused _decompress_xla formulation elsewhere).
    decode_impl: Impl = "auto"
    decode_batch_max: int = 8
    # serve-path collective experiment (§Perf falcon_gatherc/prefill
    # iterations): force the FSDP all-gather to move the COMPRESSED stream by
    # pinning the dense view to TP-only sharding.  MEASURED VERDICT: neutral
    # for decode (XLA already gathers the compressed operands), and a large
    # REGRESSION for prefill (the pinned dense view replicates decompress
    # traffic across the data axis) — so the shipped default is False and the
    # decode-serving win comes from TP-only weight rules instead
    # (falcon_tponly, 4.5x).
    gather_compressed: bool = False
    # TP serving (PR 8): route decode-shaped compressed matmuls through the
    # explicit sparse ring (dist.collectives.collective_matmul_ag_sparse)
    # when an axis_rules mesh with a "model" axis is active — the compressed
    # shard is what rotates between devices, decompress happens locally at
    # each consumer (the paper's Fig 12 traffic property, cluster-scale).
    # Falls back to the local xla path per call-site when the output dim
    # doesn't divide over the mesh or no mesh is active, so the flag is safe
    # to leave on for mixed-size models.
    decode_ring: bool = False

    def applies(self, in_dim: int, out_dim: int) -> bool:
        return (self.enabled and self.mode != "dense"
                and in_dim % self.m == 0
                and min(in_dim, out_dim) >= self.min_dim)


def _decompress_xla(values: jax.Array, indices: jax.Array, n: int, m: int,
                    k: int) -> jax.Array:
    """Slot-loop decompress (same order as the kernel's VMEM decompress);
    all temporaries [O, K] and elementwise -> fuses to one XLA pass."""
    o, nnz = values.shape
    nb = k // m
    vals3 = values.reshape(o, n, nb)
    idx3 = indices.reshape(o, n, nb).astype(jnp.int32)
    kpos = jax.lax.broadcasted_iota(jnp.int32, (o, k), 1) % m
    dense = jnp.zeros((o, k), dtype=values.dtype)
    for s in range(n):
        val_s = jnp.repeat(vals3[:, s], m, axis=1)
        idx_s = jnp.repeat(idx3[:, s], m, axis=1)
        dense = dense + jnp.where(idx_s == kpos, val_s, jnp.zeros((), values.dtype))
    return dense


def _xwt_xla(x, values, indices, n, m, gather_compressed=True):
    w = _decompress_xla(values, indices, n, m, x.shape[-1])
    if gather_compressed:
        # pin the dense view to TP-only sharding: the cross-FSDP transfer
        # then happens on the compressed operands (0.56x bytes at 2:4)
        from repro.dist.api import constrain
        w = constrain(w, "tp", None)
    return jnp.einsum("...k,ok->...o", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _xwt_xla_gather(x, values, indices, n, m):
    """Gather-MAC: true N/M flops (Alg 6 executed by XLA)."""
    o, nnz = values.shape
    blk = (jnp.arange(nnz, dtype=jnp.int32) % (nnz // n)) * m
    full_idx = blk[None, :] + indices.astype(jnp.int32)      # [o, nnz]
    xg = jnp.take(x, full_idx, axis=-1)                      # [..., o, nnz]
    y = jnp.einsum("...oe,oe->...o", xg.astype(jnp.float32),
                   values.astype(jnp.float32))
    return y.astype(x.dtype)


def nm_rerank(values: jax.Array, indices: jax.Array, n: int, m: int,
              keep: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Re-rank an n:m compressed tensor down to keep:m (the sparsity ladder).

    Within each m-block the n stored entries are re-ranked by magnitude and
    only the ``keep`` largest survive — exactly the offline ``compress`` rule
    applied to the *already-compressed* operands, so the result is a valid
    keep:m pair (in-block column order preserved) without ever touching the
    dense weight.  This is the draft-view constructor of self-speculative
    decoding: the same weight pool read at a cheaper fidelity through the
    same nm_spmv index stream, at keep/n the values+index bytes.

    values [..., rows, nnz], indices int [..., rows, nnz] (slot-planar, as
    produced by ``sparsity.compress``) -> the same layout with
    nnz' = nnz // n * keep."""
    if not 0 < keep < n:
        raise ValueError(f"need 0 < keep < n, got keep={keep} n={n}")
    nnz = values.shape[-1]
    if nnz % n:
        raise ValueError(f"nnz {nnz} not divisible by n={n}")
    g = nnz // n
    slots = lambda a: [a[..., s * g:(s + 1) * g] for s in range(n)]  # noqa: E731
    # top-|keep| per block, ties to the lowest slot; kept slots stay in slot
    # order, which is ascending in-block column order (the compress invariant)
    vs, ix = top_planes(slots(values), slots(indices), keep, indices.dtype)
    return jnp.concatenate(vs, -1), jnp.concatenate(ix, -1)


def _kernels_apply() -> bool:
    """The Pallas kernels serve on a TPU outside a multi-device mesh: GSPMD
    cannot partition a Pallas call, so sharded jits take the XLA path."""
    from repro.dist.api import current_mesh
    mesh = current_mesh()
    return (jax.default_backend() == "tpu"
            and (mesh is None or mesh.size == 1))


def default_impl(x_shape: Tuple[int, ...]) -> Impl:
    return "pallas" if _kernels_apply() else "xla"


def is_decode_shape(x_shape: Tuple[int, ...], batch_max: int = 8) -> bool:
    """True when x is decode-shaped: a single-token step [..., 1, K] (the
    serve engine's [B, 1, d] activations) or a rank-2 small-batch matvec."""
    if len(x_shape) >= 3:
        return x_shape[-2] == 1
    return len(x_shape) == 2 and x_shape[0] <= batch_max


def select_impl(cfg: SparsityConfig, x_shape: Tuple[int, ...]) -> Impl:
    """The execution policy for compressed params: one decision point shared
    by every SparseLinear (attention/MLP/SSM projections, stacked scans).

    An explicitly pinned ``cfg.impl`` always wins.  Under 'auto', decode-
    shaped inputs route to the spmv path — the pallas vindexmac kernel on
    TPU, the fused slot-loop decompress ('xla') on other backends and under
    a multi-device mesh — and prefill/training shapes keep the nm_spmm tile
    path (pallas on TPU, 'xla' otherwise).
    """
    if cfg.impl != "auto":
        return cfg.impl
    if is_decode_shape(x_shape, cfg.decode_batch_max):
        if cfg.decode_ring and _ring_mesh() is not None:
            return "ring"
        if cfg.decode_impl != "auto":
            return cfg.decode_impl
        return "spmv" if _kernels_apply() else "xla"
    return default_impl(x_shape)


def _ring_mesh():
    """The active axis_rules mesh, if it has the serving TP axis."""
    from repro.dist.api import current_mesh
    mesh = current_mesh()
    if mesh is not None and "model" in getattr(mesh, "shape", {}):
        return mesh
    return None


def _xwt_ring(x, values, indices, n, m, gather_compressed=True):
    """Sparse ring collective matmul; local-xla fallback when the shard
    doesn't fit the mesh (output rows must split evenly over "model")."""
    mesh = _ring_mesh()
    o = values.shape[-2]
    if mesh is None or mesh.shape["model"] == 1 or o % mesh.shape["model"]:
        return _xwt_xla(x, values, indices, n, m,
                        gather_compressed=gather_compressed)
    from repro.dist.collectives import ring_sparse_linear
    return ring_sparse_linear(x, values, indices, n, m, mesh, axis="model")


def nm_matmul(x: jax.Array, sp: NMSparse, impl: Impl = "auto",
              gather_compressed: bool = True) -> jax.Array:
    """Y = x @ W_sp.T (layer orientation). x [..., K], sp dense_shape [O, K]."""
    n, m = sp.n, sp.m
    if impl == "auto":
        impl = default_impl(x.shape)
    if impl == "ref":
        lead = x.shape[:-1]
        y = kref.nm_xwt_ref(x.reshape(-1, x.shape[-1]), sp.values, sp.indices, n, m)
        return y.reshape(*lead, -1)
    if impl == "xla":
        return _xwt_xla(x, sp.values, sp.indices, n, m,
                        gather_compressed=gather_compressed)
    if impl == "ring":
        return _xwt_ring(x, sp.values, sp.indices, n, m,
                         gather_compressed=gather_compressed)
    if impl == "xla_gather":
        return _xwt_xla_gather(x, sp.values, sp.indices, n, m)
    if impl == "pallas":
        return kops.nm_xwt(x, sp.values, sp.indices, n, m)
    if impl == "spmv":
        return kops.nm_spmv(x, sp.values, sp.indices, n, m)
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# SR-STE sparse training: forward through the pruned weights, straight-through
# dense gradient + decay on the pruned complement.  ``ste_sparsify`` acts on
# the *weight only*, so it composes with any contraction (plain linears, MoE
# expert einsums, conv-as-GEMM) — the mask recompute + decay live in its vjp.
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def ste_sparsify(w: jax.Array, n: int, m: int, lam: float) -> jax.Array:
    return w * nm_mask(w, n, m).astype(w.dtype)


def _stes_fwd(w, n, m, lam):
    mask = nm_mask(w, n, m).astype(w.dtype)
    return w * mask, (w, mask)


def _stes_bwd(n, m, lam, res, g):
    w, mask = res
    # straight-through dense gradient + SR-STE decay pulling pruned weights
    # toward zero so the mask anneals stably.
    dw = g + (lam * ((1.0 - mask) * w)).astype(g.dtype)
    return (dw.astype(w.dtype),)


ste_sparsify.defvjp(_stes_fwd, _stes_bwd)


def nm_matmul_ste(x: jax.Array, w: jax.Array, n: int, m: int,
                  lam: float) -> jax.Array:
    """y = x @ sparsify(w).T with straight-through training semantics."""
    return jnp.einsum("...k,ok->...o", x, ste_sparsify(w, n, m, lam),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def masked_matmul(x: jax.Array, w: jax.Array, mask: jax.Array) -> jax.Array:
    """Fixed-mask (ASP-style fine-tuning) path; autodiff gives masked grads."""
    return jnp.einsum("...k,ok->...o", x, w * mask.astype(w.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def dense_forward_view(p, sp: SparsityConfig) -> jax.Array:
    """Dense view [..., out, in] of a dense-stored linear param dict, with
    the same forward semantics ``linear_apply`` uses: srste recomputes the
    mask with STE grads, fixed applies the stored mask, and dense params
    under a not-yet-converted 'compressed' policy get the magnitude N:M mask
    (never silently unmasked).  One helper shared by the MoE stacked einsums
    and the MLA absorbed-decode path, so those paths cannot diverge from the
    per-linear one."""
    w = p["w"]
    if not sp.applies(w.shape[-1], w.shape[-2]):
        return w
    if "mask" in p:
        return w * p["mask"].astype(w.dtype)
    if sp.mode == "srste":
        return ste_sparsify(w, sp.n, sp.m, sp.srste_lam)
    if sp.mode == "compressed":
        return w * nm_mask(w, sp.n, sp.m).astype(w.dtype)
    return w
