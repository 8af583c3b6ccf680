"""The benchmark's one door into the program under test.

Everything the benchmark takes from ``repro`` goes through here: the
model configuration, the compressed parameter tree (built from the
benchmark's own dense weights by the program's packing pass, laid out by
the model's family in ``bench/families``) and the serving engine, built
as the main serving path runs: compressed 2:4 linears, paged KV, fused
paged attention, prewarmed buckets, no compile while serving.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import families


def arch_config(model: dict, check: bool = True):
    """The program's ``ArchConfig`` for a configuration file, as its family
    (``bench/families``) reads it.  With ``check``, every width must equal
    the program's own entry for the architecture."""
    from repro.configs import get_config
    fam = families.family(model)
    base = get_config(model["program_arch"])
    kw = fam.arch_fields(model, base, check)
    cfg = base.replace(dtype=model.get("torch_dtype", base.dtype), **kw)
    sp = model["sparsity"]
    if (cfg.sparsity.n, cfg.sparsity.m) != (sp["n"], sp["m"]):
        raise ValueError(f"sparsity {sp} differs from the program's "
                         f"{cfg.sparsity.n}:{cfg.sparsity.m}")
    return cfg


def params_fn(cfg, model: dict):
    """A jitted ``key -> params``: the whole compressed serving tree, made
    on the device in one executable, laid out by the model's family."""
    from repro.core.layers import convert_to_compressed
    from repro.models import convert_to_compressed as convert_model
    from repro.models import init_model
    fam = families.family(model)
    sp = cfg.sparsity

    def pack(w):
        return convert_to_compressed({"w": w}, sp)

    def build(key):
        return fam.params(key, model, cfg, pack)

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
