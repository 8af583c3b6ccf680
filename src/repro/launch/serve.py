"""Serving CLI: thin driver over the ``repro.serve`` subsystem.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --slots 4 --prompt-len 32 --gen 16 --scheduler continuous \
      --weights compressed --kv paged --block-size 8

``--scheduler sequential`` runs the fixed-batch oracle loop (the whole batch
decodes in lockstep until its slowest member finishes); ``continuous`` runs
the slot-refilling engine.  ``--weights compressed`` (the default) serves
from the compressed N:M pool — the model is packed offline at engine init
(``models.convert_to_compressed``) and decode streams w_vals + packed
col_idx through the nm_spmv policy route; ``--weights dense`` serves the
same weights unconverted (masked-dense forward), emitting identical tokens
at ~M/N the decode weight traffic.  ``--kv paged`` swaps the slot-per-row
cache for the block-pool layout of ``repro.serve.paged`` (block-table
indirection, block-aware admission, bucketed prefill); ``--kv slotted``
(the default) keeps the PR-2 layout and is the token-equality oracle.
``--attn fused`` (paged only) reads the KV pool through the flash-decoding
Pallas kernel that walks the block table in-kernel; ``--attn gather`` (the
default) materializes each slot's stream into a dense layout first and is
the oracle the fused path is tested against (see docs/serve.md, "decode
attention paths").
``--prefix-cache`` (paged only) keeps retired requests' KV blocks in a radix
index keyed on prompt tokens: admissions whose prompt shares a cached prefix
point their block table at the resident blocks (refcounted, copy-on-write)
and skip prefill for the shared span.  ``--preempt suspend`` swaps a
pool-exhaustion victim's KV to host numpy and resumes it bit-exact instead
of replaying from prefill (the ``replay`` default).
``--tp N`` (or ``--mesh model=N``) serves tensor-parallel over the first N
devices (continuous scheduler only): params and KV pools shard under
``dist.api.SERVE_TP_RULES``, tokens stay identical to the single-device
run, and with ``--weights compressed`` the decode forward rides the sparse
ring collective so only compressed bytes cross the interconnect.  Works
single-process on CPU via
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set it before
launching — jax fixes its device list at backend init), and multi-process
via ``--distributed`` (``jax.distributed.initialize``; pass
``--coordinator host:port --num-processes P --process-id I`` explicitly or
let jax pick them up from the cluster environment).
jax's persistent compilation cache is on (``--no-compile-cache`` turns it
off): in ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else in the
repo's ``.cache/xla``, so every executable this process builds is reused by
the next one — a warm relaunch of the same config skips XLA compilation.  ``--prewarm``
(continuous only) AOT-compiles the engine's complete executable set —
decode, every prefill bucket, propose/verify under ``--spec`` — at init,
before any request is admitted, so no serving tick ever traces; the
compile line printed after the run reports the bill (prewarmed executables,
trace+compile seconds, mid-serve compiles — 0 when prewarm covered the
trace — and first vs steady tick latency).
``serve`` is kept as the PR-1 API (fixed batch of identical requests) for
the examples and the integration tests.
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

import jax

from repro.configs import get_config
from repro.dist.api import make_serve_mesh
from repro.models import convert_to_compressed, init_model
from repro.serve import (ServeEngine, SpecConfig, serve_fixed_batch,
                         serve_sequential,
                         shared_prefix_trace, synthetic_trace)
from repro.serve.cache import seed_decode_caches as _seed_caches  # compat


def _parse_mesh(spec: str):
    """'axis=size[,axis=size]' -> a Mesh over jax.devices() in that order.
    Serving requires a 'model' axis (the TP/ring axis); extra axes are
    allowed but the serve rules replicate over them."""
    import numpy as np
    from jax.sharding import Mesh
    names, sizes = [], []
    for part in spec.split(","):
        name, _, size = part.partition("=")
        if not size or not size.isdigit():
            raise SystemExit(f"--mesh: bad entry {part!r} "
                             f"(want axis=size, e.g. model=4)")
        names.append(name.strip())
        sizes.append(int(size))
    if "model" not in names:
        raise SystemExit("--mesh must include a 'model' axis (the serving "
                         "TP axis)")
    n = int(np.prod(sizes))
    devs = jax.devices()
    if n > len(devs):
        raise SystemExit(f"--mesh needs {n} devices, have {len(devs)}; on "
                         f"CPU set XLA_FLAGS="
                         f"--xla_force_host_platform_device_count={n}")
    return Mesh(np.array(devs[:n]).reshape(sizes), tuple(names))


def _load(arch: str, smoke: bool, impl: str, seed: int = 0,
          mode: str = "compressed"):
    cfg = get_config(arch, smoke=smoke)
    cfg = cfg.replace(sparsity=dataclasses.replace(
        cfg.sparsity, mode=mode, impl=impl))
    params, _ = init_model(jax.random.PRNGKey(seed), cfg)
    return cfg, params


def serve(arch: str, smoke: bool, batch: int, prompt_len: int, gen: int,
          seed: int = 0, impl: str = "xla"):
    """PR-1 compatible fixed-batch serve: returns (tokens [B, gen],
    t_prefill_seconds, t_decode_seconds_per_token)."""
    cfg, params = _load(arch, smoke, impl, seed)
    reqs = synthetic_trace(cfg, n_requests=batch, prompt_len=prompt_len,
                           gen_lens=[gen], seed=seed)
    results, stats = serve_fixed_batch(params, cfg, reqs)
    toks = np.stack([results[r.rid].tokens for r in reqs])
    return toks, stats["t_prefill"], stats["t_per_decode"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--scheduler", default="sequential",
                    choices=["sequential", "continuous"])
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length (default: one batch of --slots)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--gen-mix", default="",
                    help="comma list of gen budgets cycled over the trace")
    ap.add_argument("--arrival-every", type=int, default=0)
    ap.add_argument("--impl", default="auto",
                    help="sparse-matmul impl ('auto' engages the decode "
                         "routing policy: spmv for decode shapes, spmm tiles "
                         "for prefill)")
    ap.add_argument("--weights", default="compressed",
                    choices=["dense", "compressed"],
                    help="'compressed' packs the model at engine init and "
                         "serves from the compressed pool; 'dense' serves "
                         "the unconverted masked-dense weights")
    ap.add_argument("--kv", default="slotted", choices=["slotted", "paged"],
                    help="'paged' serves through the block-table KV pool "
                         "(continuous scheduler only); 'slotted' is the "
                         "whole-row oracle layout")
    ap.add_argument("--attn", default="gather", choices=["gather", "fused"],
                    help="paged decode attention read: 'fused' walks the "
                         "block table inside the flash-decoding kernel "
                         "(in-kernel indexed K/V tile loads, online softmax "
                         "over blocks); 'gather' is the dense-gather oracle "
                         "(requires --kv paged for 'fused')")
    ap.add_argument("--block-size", type=int, default=8,
                    help="paged pool: positions per KV block")
    ap.add_argument("--blocks", type=int, default=0,
                    help="paged pool: physical block count incl. the trash "
                         "block (0 = full provisioning)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged only: keep retired requests' KV blocks in a "
                         "radix index over prompt tokens; admissions whose "
                         "prompt shares a cached prefix skip prefill for the "
                         "shared span (refcounted blocks, copy-on-write)")
    ap.add_argument("--preempt", default="replay",
                    choices=["replay", "suspend"],
                    help="paged pool-exhaustion policy: 'replay' requeues the "
                         "victim and replays it from prefill; 'suspend' swaps "
                         "its KV blocks + slot state to host numpy and "
                         "resumes bit-exact on readmission")
    ap.add_argument("--spec", action="store_true",
                    help="self-speculative decoding (paged + continuous "
                         "only): a cheap draft view of the serving pool "
                         "proposes --spec-k tokens per slot per tick, the "
                         "target verifies all of them in one batched "
                         "forward, and greedy acceptance keeps the emitted "
                         "tokens bitwise identical to the non-speculative "
                         "engine")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="with --spec: draft tokens proposed per verify")
    ap.add_argument("--draft", default="rerank",
                    choices=["rerank", "skip"],
                    help="with --spec: draft view — 'rerank' re-ranks the "
                         "compressed N:M pool to its top-1-of-m values "
                         "(needs --weights compressed), 'skip' strides over "
                         "every other layer stack")
    ap.add_argument("--prefix-mix", type=int, default=1,
                    help="with --prefix-cache: number of distinct shared "
                         "system prompts in the generated trace (the trace "
                         "becomes shared-prefix: 3/4 of --prompt-len shared, "
                         "1/4 per-request suffix)")
    ap.add_argument("--tp", type=int, default=0,
                    help="tensor-parallel width: serve over the first N "
                         "devices on a ('model',) mesh (0 = single-device; "
                         "continuous scheduler only).  CPU CI: set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--mesh", default="",
                    help="explicit mesh as 'axis=size[,axis=size]', e.g. "
                         "'model=4'; must include a 'model' axis.  "
                         "Overrides --tp")
    ap.add_argument("--tp-collective", default="auto",
                    choices=["auto", "ring", "gspmd"],
                    help="TP forward-pass collective for compressed weights: "
                         "'ring' streams the compressed N:M shards through "
                         "collective_matmul_ag_sparse, 'gspmd' leaves layout "
                         "to the partitioner, 'auto' = ring when compressed")
    ap.add_argument("--no-compile-cache", dest="compile_cache",
                    action="store_false",
                    help="do not persist compiled executables (by default "
                         "jax's compilation cache keeps them in "
                         "$JAX_COMPILATION_CACHE_DIR, else the repo's "
                         ".cache/xla)")
    ap.add_argument("--prewarm", action="store_true",
                    help="AOT-compile the engine's complete executable set "
                         "(decode, every prefill bucket, propose/verify "
                         "under --spec) at init, before any admission — "
                         "steady-state ticks never trace (continuous "
                         "scheduler only)")
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() before touching "
                         "devices (multi-process serving; the mesh then "
                         "spans the global device list)")
    ap.add_argument("--coordinator", default=None,
                    help="with --distributed: coordinator host:port")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="with --distributed: total process count")
    ap.add_argument("--process-id", type=int, default=None,
                    help="with --distributed: this process's rank")
    args = ap.parse_args()

    if (args.prefix_cache or args.preempt != "replay") and (
            args.kv != "paged" or args.scheduler != "continuous"):
        raise SystemExit("--prefix-cache/--preempt suspend require --kv paged "
                         "with --scheduler continuous (both operate on the "
                         "block pool)")
    if (args.tp or args.mesh) and args.scheduler != "continuous":
        raise SystemExit("--tp/--mesh require --scheduler continuous (the "
                         "sequential oracle is single-device by design)")
    if args.spec:
        if args.kv != "paged" or args.scheduler != "continuous":
            raise SystemExit("--spec requires --kv paged with --scheduler "
                             "continuous (speculative rollback rewinds the "
                             "block table)")
        if args.tp or args.mesh:
            raise SystemExit("--spec does not support --tp/--mesh yet")
        if args.draft == "rerank" and args.weights != "compressed":
            raise SystemExit("--draft rerank re-ranks the compressed pool: "
                             "use --weights compressed (or --draft skip)")
    if args.prewarm and args.scheduler != "continuous":
        raise SystemExit("--prewarm requires --scheduler continuous (the "
                         "sequential oracle has no enumerable shape set)")
    if args.compile_cache:
        # before any jit runs (init_model, conversion) so even the one-shot
        # init executables land in the persistent cache
        from repro.serve import enable_compile_cache
        print(f"compile cache: {enable_compile_cache()}")
    if args.distributed:
        # must run before any jax.devices()/computation: the coordinator
        # handshake fixes the global device list
        jax.distributed.initialize(coordinator_address=args.coordinator,
                                   num_processes=args.num_processes,
                                   process_id=args.process_id)
    mesh = _parse_mesh(args.mesh) if args.mesh else (
        make_serve_mesh(args.tp) if args.tp else None)

    # weights are born dense (srste semantics) so both --weights settings
    # serve literally the same model: 'compressed' packs it offline.
    cfg, params = _load(args.arch, args.smoke, args.impl, mode="srste")
    compressed = args.weights == "compressed"
    gen_lens = ([int(g) for g in args.gen_mix.split(",")] if args.gen_mix
                else [args.gen])
    n_req = args.requests or args.slots
    if args.prefix_cache:
        pre = max(1, args.prompt_len * 3 // 4)
        reqs = shared_prefix_trace(cfg, n_requests=n_req, prefix_len=pre,
                                   suffix_len=args.prompt_len - pre,
                                   gen_lens=gen_lens,
                                   arrival_every=args.arrival_every,
                                   n_prefixes=args.prefix_mix)
    else:
        reqs = synthetic_trace(cfg, n_requests=n_req,
                               prompt_len=args.prompt_len, gen_lens=gen_lens,
                               arrival_every=args.arrival_every)
    max_len = args.prompt_len + max(gen_lens)

    if args.scheduler == "continuous":
        eng = ServeEngine(params, cfg, n_slots=args.slots, max_len=max_len,
                          compressed=compressed, kv=args.kv,
                          block_size=args.block_size,
                          n_blocks=args.blocks or None, attn=args.attn,
                          prefix_cache=args.prefix_cache,
                          preempt=args.preempt, mesh=mesh,
                          tp_collective=args.tp_collective,
                          spec=(SpecConfig(k=args.spec_k, draft=args.draft)
                                if args.spec else None),
                          prewarm=args.prewarm)
        results = eng.run(reqs)
        st = eng.stats()
        mode = "prewarmed" if args.prewarm else "lazy"
        print(f"compile[{mode}]: {int(st['prewarmed_executables'])} "
              f"prewarmed + {int(st['mid_serve_compiles'])} mid-serve of "
              f"{int(st['executables_expected'])} expected executables, "
              f"{st['compile_seconds']:.2f}s compile bill "
              f"(bring-up {st['init_seconds']:.2f}s), first tick "
              f"{st['first_tick_s'] * 1e3:.1f}ms vs steady "
              f"{st['steady_tick_s'] * 1e3:.1f}ms")
        print(f"continuous[{args.weights},{args.kv},{args.attn}]: "
              f"{int(st['tokens'])} tokens in "
              f"{int(st['decode_steps'])} decode steps, "
              f"occupancy {st['occupancy']:.2f}, "
              f"weight stream {st['weight_stream_ratio']:.2f}x dense "
              f"({int(st['weight_stream_bytes'])} B/step)")
        if mesh is not None:
            print(f"tensor-parallel: tp={int(st['tp'])} over "
                  f"{tuple(mesh.axis_names)} mesh, ring traffic "
                  f"{st['ring_traffic_ratio']:.2f}x dense "
                  f"({int(st['ring_bytes_per_step'])} B/step across "
                  f"{int(st['ring_linears'])} ring linears, "
                  f"{int(st['local_linears'])} local)")
        if args.kv == "paged":
            print(f"paged pool: {int(st['kv_bytes_peak'])} B KV peak of "
                  f"{int(st['kv_bytes_capacity'])} B capacity, "
                  f"{int(st['prefill_compiles'])} prefill shapes, "
                  f"{int(st['preemptions'])} preemptions "
                  f"({args.preempt}: {int(st['swap_outs'])} swap-outs)")
        if args.prefix_cache:
            print(f"prefix cache: {int(st['prefix_hits'])} hits / "
                  f"{int(st['prefill_calls'])} prefills, "
                  f"{int(st['prefix_hit_tokens'])} cached tokens reused, "
                  f"{int(st['cow_copies'])} COW copies, "
                  f"{int(st['index_blocks'])} blocks resident in index")
        if args.spec:
            print(f"speculative[{args.draft},k={args.spec_k}]: "
                  f"acceptance {st['spec_acceptance']:.2f} "
                  f"({int(st['spec_accepted'])}/{int(st['spec_proposed'])} "
                  f"drafts), {int(st['spec_steps_saved'])} target steps "
                  f"saved over {int(st['draft_steps'])} draft steps, "
                  f"draft stream "
                  f"{st['draft_stream_bytes'] / st['weight_stream_bytes']:.2f}x "
                  f"target")
    else:
        if args.kv == "paged":
            raise SystemExit("--kv paged requires --scheduler continuous "
                             "(the sequential oracle is slotted by design)")
        if args.attn == "fused":
            raise SystemExit("--attn fused requires --kv paged with "
                             "--scheduler continuous (the fused kernel reads "
                             "through the block table)")
        if compressed:
            params = convert_to_compressed(params, cfg)
            cfg = cfg.replace(sparsity=dataclasses.replace(
                cfg.sparsity, mode="compressed"))
        results, stats = serve_sequential(params, cfg, reqs, args.slots,
                                          max_len=max_len)
        toks = sum(len(r.tokens) for r in results.values())
        print(f"sequential[{args.weights}]: {toks} tokens in "
              f"{int(stats['decode_steps'])} decode steps")
    rid0 = min(results)
    print("sample:", results[rid0].tokens[:12].tolist())


if __name__ == "__main__":
    main()
