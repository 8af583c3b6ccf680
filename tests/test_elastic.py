"""Elastic scaling: a checkpoint written under mesh A restores and continues
training under mesh B (the node-failure recovery contract)."""

from conftest import run_child

_CODE = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.dist.api import axis_rules, make_shardings
from repro.launch import steps as steps_mod
from repro.models import init_model
from repro.optim import AdamWConfig, adamw_init

phase, ckpt_dir, ndev_data = sys.argv[1], sys.argv[2], int(sys.argv[3])
cfg = get_config("llama3.2-1b", smoke=True).replace(n_layers=2, grad_accum=1)
ocfg = AdamWConfig(master_weights=False)
mesh = jax.make_mesh((ndev_data, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
mgr = CheckpointManager(ckpt_dir)

with axis_rules(mesh):
    params, pspecs = init_model(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params, ocfg)
    psh = make_shardings(pspecs, mesh, shapes_tree=params)
    step = jax.jit(steps_mod.make_train_step(cfg, ocfg, param_specs=pspecs))
    if phase == "resume":
        s = mgr.latest_step()
        (params, opt), meta = mgr.restore(s, (params, opt))
        params = jax.device_put(params, psh)   # reshard under the NEW mesh
    else:
        params = jax.device_put(params, psh)
    start = mgr.latest_step() or 0
    losses = []
    for i in range(start, start + 2):
        rng = np.random.default_rng(i)
        toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)
        batch = {"tokens": toks, "labels": (toks + 1) % cfg.vocab}
        params, opt, m = step(params, opt, batch, jnp.int32(i))
        losses.append(float(m["loss"]))
    if phase == "train":
        mgr.save(2, (params, opt), blocking=True)
    print(json.dumps({"losses": losses}))
"""


def _run(phase, ckpt, ndev_data, devices):
    return run_child(_CODE, devices=devices, argv=(phase, ckpt, ndev_data))


def test_restore_under_smaller_mesh(tmp_path):
    """Train 2 steps on (4, 2); 'lose a node', resume on (2, 2): the resumed
    losses must match a continuous run bit-for-bit-ish (same data stream)."""
    ck = str(tmp_path / "ck")
    first = _run("train", ck, 4, 8)
    resumed = _run("resume", ck, 2, 4)        # degraded mesh
    # continuous reference on the original mesh
    ck2 = str(tmp_path / "ck2")
    _run("train", ck2, 4, 8)
    cont = _run("resume", ck2, 4, 8)
    assert abs(resumed["losses"][0] - cont["losses"][0]) < 5e-3, \
        (resumed, cont)
