"""pregather-FSDP accumulation (§Perf iteration): numerically identical to
the standard path; collective volume independent of accumulation depth."""

import pytest
from conftest import run_child


def test_pregather_equivalence_subprocess():
    code = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.configs import get_config
from repro.dist.api import axis_rules, make_shardings
from repro.launch import steps as steps_mod
from repro.launch.hlo_cost import analyze_hlo
from repro.models import init_model
from repro.optim import AdamWConfig, adamw_init

cfg = get_config("llama3.2-1b", smoke=True).replace(n_layers=2, grad_accum=2,
                                                    remat_group=0)
ocfg = AdamWConfig(master_weights=False)
params, pspecs = init_model(jax.random.PRNGKey(0), cfg)
opt = adamw_init(params, ocfg)
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab),
         "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab)}

mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
outs = {}
with axis_rules(mesh):
    psh = make_shardings(pspecs, mesh, shapes_tree=params)
    params_s = jax.device_put(params, psh)
    for tag, pg in (("std", False), ("pre", True)):
        step = steps_mod.make_train_step(cfg, ocfg, param_specs=pspecs,
                                         pregather_fsdp=pg)
        j = jax.jit(step)
        p, _, m = j(params_s, opt, batch, jnp.int32(0))
        hc = analyze_hlo(j.lower(params_s, opt, batch,
                                 jnp.int32(0)).compile().as_text())
        outs[tag] = {"loss": float(m["loss"]),
                     "coll": hc["collective_bytes"],
                     "p0": float(jax.tree.leaves(p)[0].astype(jnp.float32).sum())}
print(json.dumps(outs))
"""
    out = run_child(code, devices=8)
    assert out["std"]["loss"] == pytest.approx(out["pre"]["loss"], rel=1e-4)
    assert out["std"]["p0"] == pytest.approx(out["pre"]["p0"], rel=1e-3)
