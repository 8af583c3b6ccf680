"""Compile-management regression net (persistent cache + AOT prewarm, PR 10).

Load-bearing property: ``ServeEngine(prewarm=True)`` AOT-compiles the
complete ``executable_shapes()`` set at init, **before any admission**, and
then serves an arbitrary admissible trace with **zero mid-serve compiles**
— under ``strict_prewarm=True`` a single mid-serve compile raises, so the
equivalence runs here are hard proofs, not counter checks.  Prewarming must
never change *what* is computed: tokens stay identical to the lazy engine.
Around it: the compile accounting itself (decode/prefill/propose/verify
counters, prewarm-vs-serve phases), the single-source shape enumeration
(admission ⊆ buckets ⊆ prewarmed), the persistent compilation cache
(second process over the same dir brings up strictly faster), and the TP=2
forced-host-device child (AOT executables bake in the mesh shardings and
keep dispatching across donated-cache ticks).
"""

import dataclasses
import os

import jax
import numpy as np
import pytest

from conftest import run_child

from repro.configs import get_config
from repro.models import init_model
from repro.serve import (ServeEngine, SpecConfig, shared_prefix_trace,
                         synthetic_request)
from repro.serve.prewarm import (CompileLog, JitEntry, _shape_key,
                                 abstract_batch)

_MODELS = {}


def _model(arch="llama3.2-1b"):
    if arch not in _MODELS:
        cfg = get_config(arch, smoke=True)
        cfg = cfg.replace(sparsity=dataclasses.replace(
            cfg.sparsity, mode="compressed", impl="xla"))
        params, _ = init_model(jax.random.PRNGKey(0), cfg)
        _MODELS[arch] = (cfg, params)
    return _MODELS[arch]


def _trace(cfg, plens, gens, seed=5, spec_off=()):
    rng = np.random.default_rng(seed)
    reqs = []
    for i, (p, g) in enumerate(zip(plens, gens)):
        r = synthetic_request(cfg, rng, rid=i, prompt_len=p, max_new_tokens=g)
        if i in spec_off:
            r = dataclasses.replace(r, spec=False)
        reqs.append(r)
    return reqs


def _tokens(results):
    return {rid: r.tokens.tolist() for rid, r in results.items()}


# ------------------------------------------------------- zero-trace serving

def test_mixed_trace_prewarmed_zero_mid_serve_compiles():
    """The tentpole: a mixed trace — paged pool, prefix-cache hits,
    speculation with per-request opt-outs — served by a strict prewarmed
    engine (any mid-serve compile raises) emits tokens identical to the
    lazy engine's."""
    cfg, params = _model()
    kw = dict(n_slots=3, max_len=24, kv="paged", block_size=4,
              prefix_cache=True, spec=SpecConfig(k=2, draft="rerank"))

    def mktrace():
        # 6 requests over 2 shared system prompts: later admissions hit
        # the prefix index (zero prefill, forced-decode suffix replay);
        # rid 2 opts out of speculation so the plain-decode row runs too
        reqs = shared_prefix_trace(cfg, n_requests=6, prefix_len=9,
                                   suffix_len=3, gen_lens=[5, 4], seed=7,
                                   n_prefixes=2)
        return [dataclasses.replace(r, spec=False) if r.rid == 2 else r
                for r in reqs]

    lazy = ServeEngine(params, cfg, **kw)
    r0 = lazy.run(mktrace())

    eng = ServeEngine(params, cfg, **kw, prewarm=True, strict_prewarm=True)
    r1 = eng.run(mktrace())

    assert _tokens(r0) == _tokens(r1)
    st = eng.stats()
    assert st["prefix_hits"] > 0           # the trace really is mixed
    assert st["mid_serve_compiles"] == 0
    assert st["prewarmed_executables"] == st["executables_expected"] > 0
    # every dispatch after prewarm hit a stored executable
    assert st["warm_calls"] > 0
    # the lazy engine paid the same executables mid-serve
    assert lazy.stats()["mid_serve_compiles"] > 0


def test_prewarm_is_idempotent_and_covers_replayed_trace():
    """A second prewarm() compiles nothing new, and a second trace over
    different admissible lengths still hits only prewarmed shapes."""
    cfg, params = _model()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=20, kv="paged",
                      block_size=4, prewarm=True, strict_prewarm=True)
    before = eng.stats()["prewarmed_executables"]
    eng.prewarm()
    assert eng.stats()["prewarmed_executables"] == before
    eng.run(_trace(cfg, [3, 11, 7], [5, 4, 6]))
    assert eng.stats()["mid_serve_compiles"] == 0


def test_strict_mode_raises_on_lazy_engine():
    """strict_prewarm without prewarm turns the first serving-tick compile
    into a hard error — the assertion mode is real, not advisory."""
    cfg, params = _model()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="paged",
                      block_size=4, strict_prewarm=True)
    with pytest.raises(RuntimeError, match="mid-serve compile"):
        eng.run(_trace(cfg, [5], [4]))


# --------------------------------------------------------- shape enumeration

def test_executable_shapes_single_source():
    """Admission, prewarm and stats all read one enumeration: the bucket
    set is closed (contains max_len), admitted prefill lengths are a
    subset of it, and prewarm built exactly the enumerated total."""
    cfg, params = _model()
    eng = ServeEngine(params, cfg, n_slots=3, max_len=24, kv="paged",
                      block_size=4, prefill_buckets=(4, 16),
                      prewarm=True, strict_prewarm=True)
    shapes = eng.executable_shapes()
    assert shapes["prefill_buckets"] == (4, 16, 24)      # max_len appended
    assert eng.prefill_buckets == shapes["prefill_buckets"]
    assert shapes["total"] == sum(shapes["entries"].values())
    assert eng.stats()["prewarmed_executables"] == shapes["total"]
    eng.run(_trace(cfg, [3, 17, 9], [4, 4, 4]))
    assert eng.prefill_lengths <= set(shapes["prefill_buckets"])
    assert eng.stats()["mid_serve_compiles"] == 0


def test_compile_counters_account_every_entry_point():
    """The satellite fix: decode/propose/verify executables show up in
    stats alongside prefill, and the lazy engine's compile bill lands in
    mid_serve_compiles."""
    cfg, params = _model()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=20, kv="paged",
                      block_size=4, spec=SpecConfig(k=2, draft="rerank"))
    # rid 1 opts out of speculation so the plain-decode row compiles too
    eng.run(_trace(cfg, [5, 9], [4, 5], spec_off=(1,)))
    st = eng.stats()
    assert st["decode_compiles"] == 1
    assert st["propose_compiles"] == 1
    assert st["verify_compiles"] == 1
    assert st["prefill_compiles"] == len(eng.prefill_lengths) > 0
    assert st["mid_serve_compiles"] == (
        st["decode_compiles"] + st["propose_compiles"]
        + st["verify_compiles"] + st["prefill_compiles"])
    assert st["compile_seconds"] > 0
    phases = {e["phase"] for e in eng.compile_events()}
    assert phases == {"serve"}


def test_abstract_batch_matches_admitted_shapes():
    """The prewarm-side shape builder and the engine's real admission
    produce the same dispatch key — the no-drift guarantee."""
    cfg, params = _model()
    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="paged",
                      block_size=4, prewarm=True, strict_prewarm=True)
    for b in eng.prefill_buckets:
        abstract = abstract_batch(cfg, b)
        assert all(v.shape[0] == 1 for v in abstract.values())
    # serving proves the keys match (strict mode would raise otherwise)
    eng.run(_trace(cfg, [6, 13], [3, 3]))
    assert eng.stats()["mid_serve_compiles"] == 0


# ----------------------------------------------------------- JitEntry units

def test_jit_entry_aot_dispatch_and_fallback_accounting():
    log = CompileLog()
    entry = JitEntry("f", lambda x: x * 2, log=log)
    a = jax.ShapeDtypeStruct((4,), np.float32)
    assert entry.aot_compile(a, label="x4")
    assert not entry.aot_compile(a, label="x4")          # idempotent
    out = entry(np.ones(4, np.float32))
    assert out.tolist() == [2.0] * 4
    assert entry.warm_calls == 1 and entry.n_compiles == 1
    log.serving = True
    entry(np.ones(8, np.float32))                        # uncovered shape
    assert entry.n_compiles == 2
    assert log.mid_serve_compiles == 1
    entry(np.ones(8, np.float32))                        # now warm
    assert entry.warm_calls == 2


def test_shape_key_ignores_dict_insertion_order():
    a = {"tokens": np.zeros((1, 4), np.int32),
         "embeds": np.zeros((1, 4, 8), np.float32)}
    b = dict(reversed(list(a.items())))
    assert _shape_key((a,)) == _shape_key((b,))
    sds = {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in a.items()}
    assert _shape_key((sds,)) == _shape_key((a,))


# ------------------------------------------------- persistent compile cache

def test_warm_cache_bringup_strictly_faster(tmp_path):
    """Two child processes prewarm the same config over one cache dir (given
    as ``$JAX_COMPILATION_CACHE_DIR``): the second one's compile() calls are
    disk hits, so its bring-up must be strictly faster than the first's."""
    code = r"""
import dataclasses, json
import jax, numpy as np
from repro.configs import get_config
from repro.models import init_model
from repro.serve import ServeEngine, enable_compile_cache, synthetic_request

enable_compile_cache()
cfg = get_config("llama3.2-1b", smoke=True)
cfg = cfg.replace(sparsity=dataclasses.replace(
    cfg.sparsity, mode="compressed", impl="xla"))
params, _ = init_model(jax.random.PRNGKey(0), cfg)
eng = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="paged",
                  block_size=4, prewarm=True, strict_prewarm=True)
rng = np.random.default_rng(1)
res = eng.run([synthetic_request(cfg, rng, rid=0, prompt_len=6,
                                 max_new_tokens=4)])
print(json.dumps({"init_s": eng.stats()["init_seconds"],
                  "tokens": res[0].tokens.tolist(),
                  "mid_serve": int(eng.stats()["mid_serve_compiles"])}))
"""
    cache = str(tmp_path / "xla")
    env = {"JAX_COMPILATION_CACHE_DIR": cache}
    cold = run_child(code, devices=1, env=env)
    assert os.listdir(cache), "persistent cache wrote nothing"
    warm = run_child(code, devices=1, env=env)
    assert cold["mid_serve"] == warm["mid_serve"] == 0
    assert warm["tokens"] == cold["tokens"]
    assert warm["init_s"] < cold["init_s"], (cold, warm)


# ------------------------------------------------------------ TP child test

def test_tp2_prewarmed_matches_oracle_zero_mid_serve():
    """TP=2 over forced host devices: the AOT executables bake in the mesh
    shardings (params/caches lowered concrete, host args abstract) and
    keep dispatching across donated-cache ticks — zero mid-serve compiles,
    tokens identical to the single-device lazy oracle."""
    code = r"""
import dataclasses, json
import numpy as np
import jax
from repro.configs import get_config
from repro.dist.api import make_serve_mesh
from repro.models import init_model
from repro.serve import ServeEngine, synthetic_trace

cfg = get_config("llama3.2-1b", smoke=True)
cfg = cfg.replace(sparsity=dataclasses.replace(
    cfg.sparsity, mode="srste", impl="auto"))
params, _ = init_model(jax.random.PRNGKey(0), cfg)
reqs = synthetic_trace(cfg, n_requests=5, prompt_len=9, gen_lens=[6, 4],
                       seed=0)
kw = dict(n_slots=3, max_len=18, compressed=True, kv="paged", block_size=4)

oracle = ServeEngine(params, cfg, **kw)
r0 = oracle.run([dataclasses.replace(r) for r in reqs])
eng = ServeEngine(params, cfg, mesh=make_serve_mesh(2), **kw,
                  prewarm=True, strict_prewarm=True)
r1 = eng.run([dataclasses.replace(r) for r in reqs])
st = eng.stats()
print(json.dumps({
    "match": all(np.array_equal(r0[r.rid].tokens, r1[r.rid].tokens)
                 for r in reqs),
    "mid_serve": int(st["mid_serve_compiles"]),
    "prewarmed": int(st["prewarmed_executables"]),
    "expected": int(st["executables_expected"]),
    "warm_calls": int(st["warm_calls"]),
}))
"""
    out = run_child(code, devices=2)
    assert out["match"], out
    assert out["mid_serve"] == 0, out
    assert out["prewarmed"] == out["expected"] > 0
    assert out["warm_calls"] > 0


# ------------------------------------------------------------- slotted path

def test_slotted_prewarm_with_explicit_prompt_lens():
    """Slotted prefill shapes are per-prompt (not enumerable from config);
    prewarm(prompt_lens=...) covers a known trace explicitly and decode is
    one pool-shaped executable either way."""
    cfg, params = _model()
    plens, gens = [5, 9, 5], [4, 3, 5]
    lazy = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="slotted")
    r0 = lazy.run(_trace(cfg, plens, gens))

    eng = ServeEngine(params, cfg, n_slots=2, max_len=16, kv="slotted",
                      strict_prewarm=True)
    eng.prewarm(prompt_lens=plens)
    r1 = eng.run(_trace(cfg, plens, gens))
    assert _tokens(r0) == _tokens(r1)
    st = eng.stats()
    assert st["mid_serve_compiles"] == 0
    assert st["decode_compiles"] == 1
    assert st["prefill_compiles"] == len(set(plens))
