"""The benchmark's one door into the program under test.

Everything the benchmark takes from ``repro`` goes through here: the
model configuration, the compressed parameter tree (built from the
benchmark's own dense weights by the program's packing pass) and the
serving engine, built as the main serving path runs: compressed 2:4
linears, paged KV, fused paged attention, prewarmed buckets, no compile
while serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

# program ArchConfig field <- configuration-file key
_FIELDS = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
           "n_heads": "num_attention_heads", "n_kv": "num_key_value_heads",
           "d_ff": "intermediate_size", "vocab": "vocab_size",
           "head_dim": "head_dim", "rope_theta": "rope_theta",
           "norm_eps": "rms_norm_eps"}
# fields the configuration file may set apart from the program's own entry
_CUT = ("n_layers", "norm_eps")


def arch_config(model: dict, check: bool = True):
    """The program's ``ArchConfig`` for a configuration file.  With
    ``check``, every width must equal the program's own entry for the
    architecture: a drift there would change the model under the cell."""
    from repro.configs import get_config
    base = get_config(model["program_arch"])
    kw = {f: type(getattr(base, f))(model[k]) if getattr(base, f) is not None
          else model[k] for f, k in _FIELDS.items()}
    if check:
        off = {f: (getattr(base, f), v) for f, v in kw.items()
               if f not in _CUT and getattr(base, f) != v}
        if off:
            raise ValueError(f"{model['name']}: configuration differs from "
                             f"the program's {model['program_arch']}: {off}")
    if base.family != "dense" or base.mla or base.act != "silu" \
            or base.qkv_bias or base.local_global_period:
        raise ValueError(f"{model['program_arch']} is not a llama-"
                         f"architecture dense model")
    cfg = base.replace(dtype=model.get("torch_dtype", base.dtype), **kw)
    sp = model["sparsity"]
    if (cfg.sparsity.n, cfg.sparsity.m) != (sp["n"], sp["m"]):
        raise ValueError(f"sparsity {sp} differs from the program's "
                         f"{cfg.sparsity.n}:{cfg.sparsity.m}")
    return cfg


def params_fn(cfg, model: dict):
    """A jitted ``key -> params``: the whole compressed serving tree, made
    on the device in one executable.  Layers are made one at a time inside
    it (``lax.map``), so no more than one dense layer is ever resident."""
    from repro.core.layers import convert_to_compressed
    from repro.models import convert_to_compressed as convert_model
    from repro.models import init_model
    sp = cfg.sparsity
    d = cfg.d_model

    def norm():
        return {"scale": jnp.ones((d,), jnp.float32)}

    def one(key, index):
        lin = {n: convert_to_compressed({"w": w}, sp)
               for n, w in weights.layer(key, model, index).items()}
        return {"ln1": norm(),
                "attn": {n: lin[n] for n in ("wq", "wk", "wv", "wo")},
                "ln2": norm(),
                "mlp": {n: lin[n] for n in ("wg", "wu", "wd")}}

    def build(key):
        layers = jax.lax.map(lambda i: one(key, i),
                             jnp.arange(cfg.n_layers, dtype=jnp.int32))
        return {"embed": {"emb": weights.embedding(key, model)},
                "layers": layers, "lnf": norm()}

    key = jax.random.PRNGKey(0)
    want = jax.eval_shape(lambda k: convert_model(init_model(k, cfg)[0], cfg),
                          key)
    got = jax.eval_shape(build, key)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or [(a.shape, a.dtype) for a in jax.tree.leaves(want)]
            != [(a.shape, a.dtype) for a in jax.tree.leaves(got)]):
        raise ValueError("the program's compressed parameter tree differs "
                         "from the one the benchmark builds")
    return jax.jit(build)


def engine(params, cfg, geometry: dict):
    from repro.serve import ServeEngine
    return ServeEngine(
        params, cfg, n_slots=geometry["n_slots"],
        max_len=geometry["max_len"], compressed=True, kv="paged",
        block_size=geometry["block_size"], n_blocks=geometry["n_blocks"],
        prefill_buckets=tuple(geometry["prefill_buckets"]), attn="fused",
        prewarm=True, strict_prewarm=True, compile_cache=True)


def prime(eng, lengths) -> int:
    """Compile, before the window, what admission compiles per prompt
    length and per bucket.  Admission runs eager JAX ops whose shapes follow
    the prompt: the batched prompt is cut to its bucket (two small
    executables per length), and the bucket's prefill caches are seeded
    into the pool (a few per bucket).  The first are run here on a dummy
    prompt of each length; the second by admitting, for every bucket, one
    prompt exactly that long with one token to serve, which admission
    emits and retires at once.  Returns the number of lengths primed."""
    lengths = sorted(set(int(x) for x in lengths))
    small = min(eng.prefill_buckets)
    for n in lengths:
        if n < small:
            raise ValueError(f"prompt length {n} is below the smallest "
                             f"bucket {small}: it would prefill padded up")
        ids = np.zeros(n, np.int32)
        cut = eng._plan(request(-1, ids, 2))[0]
        jax.block_until_ready(jnp.asarray(ids)[None][:, :cut])
    buckets = sorted({eng._plan(request(-1, np.zeros(n, np.int32), 2))[0]
                      for n in lengths})
    short = [request(-1 - i, np.zeros(b, np.int32), 1)
             for i, b in enumerate(buckets)]
    eng.run(short)
    for r in short:
        eng.results.pop(r.rid, None)
    return len(lengths)


def request(rid: int, tokens, max_new: int):
    from repro.serve import Request
    return Request(rid=rid, inputs={"tokens": tokens}, max_new_tokens=max_new)


def enable_compile_cache() -> str:
    from repro.serve import enable_compile_cache as enable
    return enable()
