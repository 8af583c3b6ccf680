"""Tensor-parallel serving: TP-sharded ServeEngine vs the single-device
oracle (subprocess, forced host devices), plus host-side unit tests for the
sharding rules themselves.

Token-equality contract: the serving rules (``dist.api.SERVE_TP_RULES``)
shard only weight output-feature axes and per-head cache axes — never a
contraction axis — so per-element reduction order matches the single-device
engine and greedy tokens must be identical per request.  Checked per family
(dense GQA, MLA + MoE, dense MoE) at TP=2, and at TP=4 / uncompressed for
the dense-GQA arch.  The compressed engines route decode linears through the
explicit sparse ring; the ring wrapper itself is checked bitwise against the
local path and for collective-permute (no all-gather) lowering.
"""

import numpy as np
import pytest

from conftest import run_child

_ENGINE_CODE = r"""
import dataclasses, json, sys
import numpy as np
import jax
from repro.configs import get_config
from repro.dist.api import make_serve_mesh
from repro.models import init_model
from repro.serve import ServeEngine, synthetic_trace

arch, tp, compressed = sys.argv[1], int(sys.argv[2]), sys.argv[3] == "1"
cfg = get_config(arch, smoke=True)
cfg = cfg.replace(sparsity=dataclasses.replace(
    cfg.sparsity, mode="srste", impl="auto"))
params, _ = init_model(jax.random.PRNGKey(0), cfg)
reqs = synthetic_trace(cfg, n_requests=5, prompt_len=9, gen_lens=[6, 4],
                       seed=0)
kw = dict(n_slots=3, max_len=18, compressed=compressed, kv="paged",
          block_size=4)

oracle = ServeEngine(params, cfg, **kw)
r0 = oracle.run([dataclasses.replace(r) for r in reqs])
eng = ServeEngine(params, cfg, mesh=make_serve_mesh(tp), **kw)
r1 = eng.run([dataclasses.replace(r) for r in reqs])
st = eng.stats()
print(json.dumps({
    "match": all(np.array_equal(r0[r.rid].tokens, r1[r.rid].tokens)
                 for r in reqs),
    "tokens": int(st["tokens"]),
    "tp": st["tp"],
    "ring_ratio": st.get("ring_traffic_ratio"),
    "ring_linears": st.get("ring_linears"),
}))
"""


@pytest.mark.parametrize("arch,tp,compressed", [
    ("llama3.2-1b", 2, True),            # dense GQA family
    ("llama3.2-1b", 4, True),
    ("llama3.2-1b", 2, False),           # uncompressed (pure GSPMD layout)
    ("deepseek-v2-lite-16b", 2, True),   # MLA attention + MoE FFN
    ("deepseek-67b", 2, True),           # dense-family MoE-scale config
])
def test_tp_tokens_match_oracle(arch, tp, compressed):
    out = run_child(_ENGINE_CODE, devices=4,
                    argv=[arch, tp, "1" if compressed else "0"])
    assert out["match"], f"TP={tp} tokens diverged from oracle: {out}"
    assert out["tokens"] > 0
    assert out["tp"] == tp
    if compressed:
        # the modeled ring traffic must show the compression win on the wire
        assert out["ring_linears"] > 0
        assert out["ring_ratio"] <= 0.6, out


def test_slotted_tp_matches_oracle():
    """The slotted (non-paged) engine shards its cache pool through the
    init_caches specs and must match its own oracle too."""
    code = _ENGINE_CODE.replace('kv="paged", block_size=4',
                                'kv="slotted"').replace(" block_size=4)", ")")
    out = run_child(code, devices=4, argv=["llama3.2-1b", 2, "1"])
    assert out["match"], out


def test_ring_linear_bitwise_and_lowering():
    """dist.collectives.ring_sparse_linear == the local decompress path, and
    lowers to collective-permute with zero all-gathers.  Equal to f32
    rounding, not bit for bit: XLA's CPU dot may order the K reduction of a
    [B, K] x [K, O/ndev] shard differently from the whole [K, O] weight."""
    code = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.sparsity import compress
from repro.core.sparse_matmul import _xwt_xla
from repro.dist.api import make_serve_mesh
from repro.dist.collectives import ring_sparse_linear

O, K, B = 128, 128, 4
w = jax.random.normal(jax.random.PRNGKey(0), (O, K))
sp = compress(w, 2, 4)
x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, K))
mesh = make_serve_mesh(4)
v = jax.device_put(sp.values, NamedSharding(mesh, P("model", None)))
i = jax.device_put(sp.indices, NamedSharding(mesh, P("model", None)))

f = jax.jit(lambda x, v, i: ring_sparse_linear(x, v, i, 2, 4, mesh))
y_ring = f(x, v, i)
y_ref = _xwt_xla(x, sp.values, sp.indices, 2, 4, gather_compressed=False)
hlo = f.lower(x, v, i).compile().as_text()
print(json.dumps({
    "max_err": float(np.abs(np.asarray(y_ring) - np.asarray(y_ref)).max()),
    "has_permute": "collective-permute" in hlo,
    "gathers": hlo.count(" all-gather("),
}))
"""
    out = run_child(code, devices=4)
    assert out["max_err"] <= 1e-5, f"ring diverged from the local path: {out}"
    assert out["has_permute"], "ring should lower to collective-permute"
    assert out["gathers"] == 0, "compressed operands must not be all-gathered"


def test_blockpool_leaf_sharding():
    """BlockPool(mesh=...) lays out paged leaves with replicated block axes
    and TP-sharded head axes; slot-indexed leaves keep their slotted spec;
    the block table stays host numpy."""
    code = r"""
import json
import jax
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs import get_config
from repro.dist.api import SERVE_TP_RULES, logical_to_pspec, make_serve_mesh
from repro.serve.paged import BlockPool

cfg = get_config("llama3.2-1b", smoke=True)
mesh = make_serve_mesh(2)
pool = BlockPool(cfg, n_slots=2, max_len=16, block_size=4, mesh=mesh)

leaves = jax.tree_util.tree_leaves(pool.caches)
specs = pool._treedef.flatten_up_to(pool.cache_specs)
checks = []
for leaf, spec, ax in zip(leaves, specs, pool._seq_axes):
    ps = leaf.sharding.spec
    expect = logical_to_pspec(spec, SERVE_TP_RULES, mesh=mesh,
                              shape=leaf.shape)
    checks.append({
        "spec": list(spec), "resolved": list(ps), "paged": ax is not None,
        "matches_rules": tuple(ps) == tuple(expect),
        "sharded": any(e is not None for e in ps),
    })
print(json.dumps({
    "checks": checks,
    "table_is_numpy": isinstance(pool.table, np.ndarray),
    "any_sharded": any(c["sharded"] for c in checks),
}))
"""
    out = run_child(code, devices=4)
    assert out["table_is_numpy"]
    assert out["any_sharded"], "no pool leaf got TP-sharded at all"
    for c in out["checks"]:
        assert c["matches_rules"], c
        if c["paged"]:
            # a paged leaf's resolved spec must never shard the collapsed
            # (n_blocks, block_size) axes — they sit where the spec says
            # (None, None), and logical_to_pspec keeps None as None
            assert c["spec"].count("act_heads") <= 1


def test_param_shard_specs_structural():
    """The spec walker keys on leaf names, so it covers both the dense tree
    and the post-conversion compressed tree (single device, no mesh)."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import (convert_to_compressed, init_model,
                              param_shard_specs)

    cfg = get_config("llama3.2-1b", smoke=True)
    # srste init keeps dense 'w' leaves; conversion renames to w_vals/w_idx
    cfg = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity,
                                                   mode="srste"))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    comp = convert_to_compressed(params, cfg.replace(
        sparsity=dataclasses.replace(cfg.sparsity, mode="compressed")))

    def flat(tree):
        return {jax.tree_util.keystr(k): v for k, v in
                jax.tree_util.tree_flatten_with_path(
                    tree, is_leaf=lambda x: isinstance(x, tuple))[0]}

    for tree in (params, comp):
        specs = flat(param_shard_specs(tree))
        leaves = flat(jax.tree.map(lambda x: x.shape, tree))
        # None (replicated) specs are dropped by pytree flatten; everything
        # that survives must be a real leaf path
        assert set(specs) <= set(leaves)
        for path in leaves:
            name = path.rsplit("'", 2)[-2] if "'" in path else ""
            if name in ("w", "w_vals", "w_idx", "mask", "emb"):
                assert path in specs, f"linear leaf {path} got no spec"
        for path, spec in specs.items():
            nd = len(leaves[path])
            name = path.rsplit("'", 2)[-2] if "'" in path else ""
            assert len(spec) == nd, (path, spec, leaves[path])
            if name in ("w", "w_vals", "w_idx", "mask"):
                # out axis sharded, contraction axis and stack axes not
                assert spec[-2] == "tp" and spec[-1] is None, (path, spec)
                assert all(s is None for s in spec[:-2]), (path, spec)
            elif name == "b":
                assert spec[-1] == "tp", (path, spec)
    # compressed leaves exist and got specs (the structural property that
    # an init-time spec tree cannot provide)
    assert any("w_vals" in p for p in flat(param_shard_specs(comp)))


def test_serve_ring_traffic_model():
    """Modeled ring traffic: compressed 2:4 f32 lands at 0.53x dense (values
    are N/M, the packed 2-bit index stream adds 1/16 of a f32 per nonzero)."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.models import (convert_to_compressed, init_model,
                              serve_ring_traffic_bytes)

    cfg = get_config("llama3.2-1b", smoke=True)
    # srste init keeps dense 'w' leaves; the conversion packs them
    cfg = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity,
                                                   mode="srste"))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)
    ccfg = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity,
                                                    mode="compressed"))
    comp = convert_to_compressed(params, ccfg)

    t = serve_ring_traffic_bytes(comp, ccfg, ndev=2)
    assert t["ring_linears"] > 0
    assert 0 < t["ring_bytes"] < t["dense_ring_bytes"]
    assert t["ratio"] <= 0.6
    # dense model over the same ring: ratio is exactly 1
    td = serve_ring_traffic_bytes(params, cfg, ndev=2)
    assert td["ratio"] == 1.0
    # single device: no ring, no traffic
    t1 = serve_ring_traffic_bytes(comp, ccfg, ndev=1)
    assert t1["ring_bytes"] == 0 and t1["ring_linears"] == 0
