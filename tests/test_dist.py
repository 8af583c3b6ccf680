"""Distribution substrate: logical rules, divisibility-aware constraints,
compressed psum, pipeline parallelism, elastic meshes.  Multi-device paths run
in subprocesses (host device count must be set before jax init)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import run_child

from repro.dist.api import (DEFAULT_RULES, MULTIPOD_RULES, axis_rules,
                            logical_to_pspec, make_shardings)
from repro.dist.elastic import degraded_meshes


def _run_child(code: str, devices: int = 8) -> dict:
    return run_child(code, devices=devices, timeout=300)


def test_logical_to_pspec():
    from jax.sharding import PartitionSpec as P
    assert logical_to_pspec(("act_batch", None, "tp"),
                            DEFAULT_RULES) == P("data", None, "model")
    assert logical_to_pspec(("act_batch",), MULTIPOD_RULES) == \
        P(("pod", "data"))


def test_degraded_meshes():
    out = degraded_meshes(256, [0, 16, 64], prefer_model=16)
    assert out[0] == (256, (16, 16))
    assert out[1][0] == 240 and out[1][1][0] * out[1][1][1] == 240


def test_constrain_divisibility_subprocess():
    code = r"""
import json
import jax, jax.numpy as jnp
from jax.sharding import AxisType, PartitionSpec as P
from repro.dist.api import axis_rules, constrain, make_shardings
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
with axis_rules(mesh):
    # kv=3 doesn't divide model=4 -> dropped; batch=8 divides data=2 -> kept
    @jax.jit
    def f(x):
        return constrain(x, "act_batch", None, "act_heads", None) * 2
    x = jnp.ones((8, 5, 3, 16))
    y = f(x)
    # axis-reuse dedupe: seq and heads both want 'model'
    @jax.jit
    def g(x):
        return constrain(x, "act_batch", "act_seq_sp", "act_heads", None) + 1
    z = g(jnp.ones((8, 4, 4, 16)))
    sh = make_shardings(("act_batch", None), mesh,
                        shapes_tree=jax.ShapeDtypeStruct((7, 3), jnp.float32))
print(json.dumps({"ok": True, "y": float(y.sum()), "z": float(z.sum()),
                  "uneven_spec": str(sh.spec)}))
"""
    out = _run_child(code, devices=8)
    assert out["ok"] and out["uneven_spec"] == "PartitionSpec()"


def test_compressed_psum_subprocess():
    code = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.optim.compression import compressed_psum
mesh = jax.make_mesh((4,), ("pod",))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))

def f(xs, method):
    return compressed_psum(xs[0], "pod", method=method)

outs = {}
for method in ("int8", "bf16"):
    g = shard_map(lambda xs: f(xs, method), mesh=mesh, in_specs=P("pod"),
                  out_specs=P())
    y = g(x)
    ref = np.mean(np.asarray(x), axis=0)
    err = float(np.abs(np.asarray(y) - ref).max())
    outs[method] = err
print(json.dumps(outs))
"""
    out = _run_child(code, devices=4)
    assert out["bf16"] < 0.02, out
    assert out["int8"] < 0.05, out


def test_pipeline_parallel_subprocess():
    code = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.dist.pipeline import pipeline_apply
mesh = jax.make_mesh((4,), ("pp",), axis_types=(AxisType.Auto,))
S, M, MB, D = 4, 8, 2, 16
ks = jax.random.split(jax.random.PRNGKey(0), S)
params = jnp.stack([jax.random.normal(k, (D, D)) * 0.2 for k in ks])

def stage(w, x):
    return jnp.tanh(x @ w)

x = jax.random.normal(jax.random.PRNGKey(1), (M, MB, D))
y = pipeline_apply(stage, params, x, mesh, axis="pp")
# sequential reference
ref = x
for s in range(S):
    ref = stage(params[s], ref.reshape(M * MB, D).reshape(M, MB, D))
    ref = jnp.stack([stage(params[s], x_) for x_ in ref]) if False else ref
ref = x
for s in range(S):
    ref = jax.vmap(lambda xb: stage(params[s], xb))(ref)
err = float(jnp.abs(y - ref).max())
print(json.dumps({"err": err}))
"""
    out = _run_child(code, devices=4)
    assert out["err"] < 1e-5, out


def test_sharded_train_step_subprocess():
    """End-to-end: jitted train_step with NamedShardings on an 8-device mesh
    matches the unsharded step numerically."""
    code = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.dist.api import axis_rules, make_shardings
from repro.launch import steps as steps_mod
from repro.models import init_model
from repro.optim import AdamWConfig, adamw_init

cfg = get_config("llama3.2-1b", smoke=True).replace(n_layers=2, grad_accum=2)
ocfg = AdamWConfig(master_weights=False)
params, pspecs = init_model(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params, ocfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)}

step = steps_mod.make_train_step(cfg, ocfg)
p_ref, _, m_ref = jax.jit(step)(params, opt, batch, jnp.int32(0))

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
with axis_rules(mesh):
    step_sh = steps_mod.make_train_step(cfg, ocfg, param_specs=pspecs)
    psh = make_shardings(pspecs, mesh, shapes_tree=params)
    params_s = jax.device_put(params, psh)
    p_s, _, m_s = jax.jit(step_sh)(params_s, opt, batch, jnp.int32(0))

dl = abs(float(m_ref["loss"]) - float(m_s["loss"]))
maxdiff = max(float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
              for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_s)))
print(json.dumps({"dloss": dl, "maxdiff": maxdiff}))
"""
    out = _run_child(code, devices=8)
    assert out["dloss"] < 1e-3, out
    assert out["maxdiff"] < 5e-2, out
