"""Quickstart: N:M structured sparsity end to end in ~60 lines.

1. prune a dense matrix to 2:4, compress it (values + 2-bit indices),
2. multiply with every implementation (ref / XLA / gather / Pallas-interpret),
3. train a small sparse LM for a few steps with SR-STE,
4. convert to the compressed serving format and decode.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import (NMSparse, SparsityConfig, compress, decompress,
                        nm_matmul, sparsify, storage_bytes)
from repro.configs import get_config
from repro.launch.serve import serve
from repro.launch.train import train_loop

print("== 1. the format =========================================")
w = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
sp = compress(w, n=2, m=4)                  # 2:4 — up to 2 nonzeros per 4
print("dense shape:", w.shape, "-> values", sp.values.shape,
      "+ 2-bit indices", sp.indices.shape)
print("storage: dense", w.size * 4, "B vs compressed",
      storage_bytes(sp, packed=True), "B")
assert jnp.allclose(decompress(sp), sparsify(w, 2, 4))

print("== 2. one matmul, four implementations ===================")
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
y_ref = nm_matmul(x, sp, impl="ref")
for impl in ("xla", "xla_gather", "pallas"):
    y = nm_matmul(x, sp, impl=impl)
    err = float(jnp.abs(y - y_ref).max())
    print(f"  {impl:18s} max|err| vs ref = {err:.2e}")
    assert err < 1e-3

print("== 3. sparse training (SR-STE) ===========================")
# synthetic-but-learnable data: next token = current token + 1 (mod V)
import jax.numpy as jnp  # noqa: E402
from repro.launch.steps import make_train_step  # noqa: E402
from repro.models import init_model  # noqa: E402
from repro.optim import AdamWConfig, adamw_init  # noqa: E402
import numpy as np  # noqa: E402

cfg = get_config("llama3.2-1b", smoke=True).replace(n_layers=2, grad_accum=1)
params, _ = init_model(jax.random.PRNGKey(0), cfg)
ocfg = AdamWConfig(master_weights=False)
opt = adamw_init(params, ocfg)
step = jax.jit(make_train_step(cfg, ocfg, base_lr=3e-3, warmup=5))
rng = np.random.default_rng(0)
losses = []
for i in range(30):
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)
    params, opt, metrics = step(params, opt,
                                {"tokens": toks,
                                 "labels": (toks + 1) % cfg.vocab},
                                jnp.int32(i))
    losses.append(float(metrics["loss"]))
print(f"loss {losses[0]:.3f} -> {losses[-1]:.3f} "
      f"(learning token+1 rule under 2:4 SR-STE)")

print("== 4. compressed serving =================================")
toks, t_prefill, t_decode = serve("llama3.2-1b", smoke=True, batch=2,
                                  prompt_len=16, gen=8)
print(f"generated {toks.shape} tokens; prefill {t_prefill*1e3:.1f} ms, "
      f"decode {t_decode*1e3:.2f} ms/tok")
print("done.")
