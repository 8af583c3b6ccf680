"""Continuous-batching serve engine: slotted or paged KV cache.

The engine owns a decode-cache pool and a per-slot int32 position vector and
interleaves two operations:

* **prefill-on-admission** — when the scheduler places a queued request into
  a freed slot, the engine prefills that request alone (batch 1), seeds a
  single-slot decode cache from the prefill caches, and installs it:
  the slotted pool scatters a batch row (``cache.scatter_slot``), the paged
  pool writes blocks through the slot's table (``paged.BlockPool.seed``).

* **batched decode** — one ``decode_step`` per tick over the whole pool with
  the per-slot position vector.  Rows whose slot is idle carry stale
  tokens/positions; slotted idle rows write into their own (dead) batch row,
  paged idle rows write into the reserved trash block, and batch rows are
  independent in every model op, so active outputs are unaffected.
  (Exception: MoE expert capacity couples rows — with ``capacity_factor``
  routing, outputs are only bit-identical to the oracle while batch
  composition matches.)

``kv="paged"`` (the tentpole of serve/paged.py) changes three things:

* **admission is block-aware** — a request is admitted while free blocks
  cover its prefill; block appends during decode are lazy (one block every
  ``block_size`` ticks per slot), and exhaustion preempts the newest active
  request back to the queue front (it restarts from prefill — greedy decode
  makes the replay deterministic).
* **prefill lengths are bucketed** — prompts prefill at the nearest bucket
  so the prefill jit compiles at most ``len(buckets)`` distinct shapes
  instead of one per prompt length.  Token-input families bucket DOWN and
  feed the remaining prompt tokens through the ordinary batched decode path
  as *forced* tokens (chunked prefill: exact, since decode recomputes the
  same K/V the full prefill would have); the embeds-input family — and any
  token prompt shorter than the smallest bucket — buckets UP with right
  padding, which causal attention keeps out of positions < prompt_len, and
  reads its logits at ``prompt_len - 1``.
* **decode reads K/V through the block table** — the jitted decode step
  takes the [n_slots, max_blocks] table as an argument; see
  ``models.attention`` for the gather-based view.

``prefix_cache=True`` (paged only) adds cross-request block sharing: retired
prompts register their (tokens -> block ids) mapping in a host-side radix
trie (``serve.prefix.PrefixIndex``), admission matches an incoming token
prompt against it, and a hit makes the new slot's table *point at* the
cached blocks (``BlockPool.share``) — the shared span costs zero prefill
steps; only the divergent suffix replays through forced decode.  The index
pins its blocks with refcounts and is evicted LRU under memory pressure;
the first decode write into a partially shared block triggers copy-on-write
(``BlockPool.cow``), so a shared block is never mutated.

``preempt="suspend"`` (paged only) replaces replay-from-prefill preemption
with suspend-to-host: the victim's owned blocks and slot-indexed state are
swapped to host numpy (``BlockPool.swap_out``) together with its scheduler
state (emitted tokens, pending prompt catch-up, position), and readmission
restores all of it (``swap_in``) instead of re-running prefill — preemption
cost scales with resident bytes instead of prompt length, and no emitted
token is ever recomputed.  ``preempt="replay"`` keeps the PR-5 behavior and
serves as the oracle (greedy decode makes replay deterministic).

``mesh=`` (PR 8) turns on tensor-parallel serving: params and cache pools
are laid out over a 1-D ("model",) device mesh under
``dist.api.SERVE_TP_RULES`` — every linear's output-feature axis and the
per-head cache axes shard, contraction axes / block tables / scalars
replicate — and both jitted entry points trace inside the matching
``axis_rules`` context so the model's ``constrain`` annotations resolve.
Because only output axes are ever split, per-element reduction order is
identical to the single-device engine, which therefore stays the
token-equality oracle.  With ``compressed=True`` the decode-shaped linears
additionally route through the explicit sparse ring
(``dist.collectives.collective_matmul_ag_sparse`` via
``sparsity.decode_ring``), so what crosses the interconnect per step is the
*compressed* weight shard — the paper's Fig 12 traffic property at cluster
scale; ``stats()`` reports the modeled ring bytes vs the dense-TP baseline.

``prewarm=True`` (PR 10) moves *compilation* out of the serving loop the
same way the paper moves index resolution out of the matmul inner loop:
``executable_shapes()`` derives the complete set of executables this
engine configuration can ever need (one decode / propose / verify shape
over the full pool width, one prefill shape per bucket — the bucket set
always contains ``max_len``, so it is closed over every admissible
prompt), and ``prewarm()`` AOT-compiles all of them at init, before any
request is admitted, registering the compiled executables for direct
dispatch (``serve.prewarm.JitEntry``) — steady-state ticks never trace.
``compile_cache=True`` additionally persists every executable across
process restarts through jax's compilation cache (``enable_compile_cache``),
so a warm bring-up pays lowering but not XLA compilation.  Every compile the
engine does pay is accounted in ``stats()`` (per-entry counters,
``mid_serve_compiles``, ``compile_seconds``, first-vs-steady tick wall
time); ``strict_prewarm=True`` turns any mid-serve compile into a hard
error — the test-mode proof that the enumerated set was complete.

This is the decode regime the paper's compressed N:M format targets: every
step is a small-batch matvec against the compressed weight stream
(``kernels.nm_spmv``'s vindexmac dataflow), so keeping slots full converts
directly into tokens per weight-stream pass — and the paged pool keeps them
full by admitting on bytes, not rows.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.api import SERVE_TP_RULES, make_shardings
from repro.models import (convert_to_compressed, decode_step, init_caches,
                          make_draft, param_shard_specs, prefill,
                          serve_ring_traffic_bytes, verify_step,
                          weight_stream_bytes)
from repro.serve import trace
from repro.serve.cache import scatter_slot, seed_decode_caches
from repro.serve.paged import BlockPool, SwapState, TRASH_BLOCK, \
    _detect_layout, default_buckets
from repro.serve.prefix import PrefixIndex
from repro.serve.prewarm import (CompileLog, JitEntry, abstract_batch,
                                 enable_compile_cache)
from repro.serve.request import Request, RequestResult
from repro.serve.scheduler import SlotScheduler
from repro.serve.speculative import SpecConfig, accept_greedy, draft_propose_k


@dataclasses.dataclass
class _SlotState:
    req: Request
    tokens: List[int]
    admitted_at: int
    # prompt tokens not yet fed (bucketed-down prefill catch-up); while
    # non-empty the slot is still consuming its prompt and emits nothing
    pending: List[int] = dataclasses.field(default_factory=list)
    # prompt replay through decode ticks: when the admission that started
    # it ended (perf_counter), and the prompt tokens fed so far
    replay_from: float = math.nan
    replayed: int = 0


@dataclasses.dataclass
class _Suspended:
    """A suspended-to-host request: swapped cache state + runtime state."""
    state: _SlotState
    swap: SwapState
    pos: int
    tok: int


class ServeEngine:
    """Continuous-batching greedy-decode engine (single host, CPU-friendly).

    ``compressed=True`` converts the whole model to the compressed N:M
    serving format at init (``models.convert_to_compressed``) and serves
    from that pool.  ``kv="paged"`` swaps the slot-per-row cache for the
    block-pool layout of ``serve.paged`` (``block_size``/``n_blocks``/
    ``prefill_buckets`` configure it); ``kv="slotted"`` keeps the PR-2
    layout and remains the token-equality oracle.  ``attn="fused"`` (paged
    only) reads the pool through the in-kernel block-table walk of
    ``kernels.flash_attention``; ``attn="gather"`` is the dense-gather
    oracle read.  ``debug_invariants=True`` cross-checks the block tables
    against the pool free list before every decode tick.

    ``compile_cache=True`` persists compiled executables across processes
    (in ``$JAX_COMPILATION_CACHE_DIR``, else the repo's ``.cache/xla`` —
    see ``serve.prewarm.enable_compile_cache``); ``prewarm=True`` AOT-compiles the engine's complete
    executable set (``executable_shapes()``) before any request is
    admitted; ``strict_prewarm=True`` hard-errors on any compile inside
    the serving loop (the ``mid_serve_compiles == 0`` assertion mode)."""

    def __init__(self, params, cfg, n_slots: int, max_len: int,
                 compressed: bool = False, kv: str = "slotted",
                 block_size: int = 4, n_blocks: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 attn: str = "gather", prefix_cache: bool = False,
                 preempt: str = "replay", debug_invariants: bool = False,
                 mesh=None, tp_collective: str = "auto",
                 spec: Optional[SpecConfig] = None,
                 compile_cache: bool = False, prewarm: bool = False,
                 strict_prewarm: bool = False):
        t_init = time.perf_counter()
        # cache config is process-global; set it before anything compiles so
        # the conversion/device_put jits below persist too
        self.compile_cache_dir = (enable_compile_cache() if compile_cache
                                  else None)
        self._compile_log = CompileLog(strict=strict_prewarm)
        self._jits: Dict[str, JitEntry] = {}
        if kv not in ("slotted", "paged"):
            raise ValueError(f"kv must be 'slotted' or 'paged', got {kv!r}")
        if tp_collective not in ("auto", "ring", "gspmd"):
            raise ValueError(f"tp_collective must be 'auto', 'ring' or "
                             f"'gspmd', got {tp_collective!r}")
        if mesh is not None and "model" not in mesh.shape:
            raise ValueError(f"serving mesh needs a 'model' axis, got "
                             f"{tuple(mesh.axis_names)} (see "
                             f"dist.api.make_serve_mesh)")
        if attn not in ("gather", "fused"):
            raise ValueError(f"attn must be 'gather' or 'fused', got {attn!r}")
        if attn == "fused" and kv != "paged":
            raise ValueError("attn='fused' requires kv='paged' (the fused "
                             "kernel reads through the block table; the "
                             "slotted layout has none)")
        if preempt not in ("replay", "suspend"):
            raise ValueError(f"preempt must be 'replay' or 'suspend', "
                             f"got {preempt!r}")
        if prefix_cache and kv != "paged":
            raise ValueError("prefix_cache=True requires kv='paged' (prefix "
                             "hits share physical blocks through the block "
                             "table; the slotted layout has none)")
        if spec is not None:
            if kv != "paged":
                raise ValueError("spec= requires kv='paged' (speculative "
                                 "rollback rewinds the block table; the "
                                 "slotted layout has none)")
            if mesh is not None:
                raise ValueError("spec= over a mesh is not supported yet "
                                 "(the draft/verify jits are untested under "
                                 "tensor-parallel layouts)")
            if (spec.draft == "rerank" and not compressed
                    and cfg.sparsity.mode != "compressed"):
                raise ValueError("spec.draft='rerank' re-ranks the compressed "
                                 "N:M pool — serve with compressed=True (or "
                                 "params already in compressed mode)")
        if compressed:
            # serve from the compressed pool: pack every SparseLinear offline
            # (the paper's compress step) and flip the policy to 'compressed'
            # so any leaf the packing skipped keeps masked-forward semantics.
            params = convert_to_compressed(params, cfg)
            cfg = cfg.replace(sparsity=dataclasses.replace(
                cfg.sparsity, mode="compressed"))
        self.mesh = mesh
        self.rules = None
        self.ring_traffic = None
        if mesh is not None:
            self.rules = dict(SERVE_TP_RULES)
            # 'auto': compressed serving rides the explicit sparse ring so
            # only compressed bytes cross the interconnect; dense serving
            # leaves layout to GSPMD (there is nothing compressed to ship).
            if compressed and tp_collective in ("auto", "ring"):
                cfg = cfg.replace(sparsity=dataclasses.replace(
                    cfg.sparsity, decode_ring=True))
            # shard AFTER conversion: the spec walker is structural (keyed on
            # leaf names), so it sees the compressed 'w_vals'/'w_idx' leaves
            # the init-time spec tree knows nothing about
            params = jax.device_put(params, make_shardings(
                param_shard_specs(params), mesh, self.rules,
                shapes_tree=params))
            self.ring_traffic = serve_ring_traffic_bytes(
                params, cfg, int(mesh.shape["model"]))
        self.compressed = compressed
        self.weight_stream = weight_stream_bytes(params, cfg)
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.kv = kv
        self.attn = attn
        self.preempt_mode = preempt
        self.debug_invariants = debug_invariants
        self.scheduler = SlotScheduler(n_slots)
        self.pos = np.zeros(n_slots, np.int32)
        self.tok = np.zeros(n_slots, np.int32)
        self.active = np.zeros(n_slots, bool)
        self.results: Dict[int, RequestResult] = {}
        self.decode_steps = 0
        self.ticks = 0
        self.preemptions = 0
        self.prefill_calls = 0               # admissions that ran a prefill
        self.prefix_hits = 0                 # admissions served from the trie
        self.prefix_hit_tokens = 0           # prompt tokens skipped via hits
        self.swap_outs = 0
        self.swap_ins = 0
        self.index_evictions = 0
        self.rejected = 0
        self.replayed_prompt_tokens = 0      # prompt tokens fed by decode
        # this engine's spans in the process-wide recorder (serve.trace)
        self.trace_id = trace.new_engine()
        self.prefill_lengths = set()         # distinct compiled prefill seqs
        self._slots: Dict[int, _SlotState] = {}
        self._suspended: Dict[int, _Suspended] = {}   # rid -> host state
        self._spec = spec
        self.spec_proposed = 0               # draft tokens offered to verify
        self.spec_accepted = 0               # draft tokens the target kept
        self.steps_saved = 0                 # target passes avoided vs oracle
        self.draft_steps = 0                 # draft-model decode steps run
        if kv == "paged":
            self.pool = BlockPool(cfg, n_slots, max_len, block_size, n_blocks,
                                  mesh=mesh, rules=self.rules)
            self.caches = None
            # prefix sharing needs every cache leaf addressable through the
            # block table: a family with slot-indexed state (SSM, conv tails,
            # encoder cross K/V) regenerates that state only in prefill, so
            # skipping prefill would resume from zeros — not cacheable.
            self._all_paged = (len(self.pool._seq_axes) > 0 and
                               all(ax is not None
                                   for ax in self.pool._seq_axes))
            self.index = PrefixIndex() if prefix_cache else None
            # max_len always rides in the bucket set so every admissible
            # prompt lands in a bucket (submit caps prompts at max_len):
            # the executable set is *closed* — what prewarm enumerates is
            # exactly what admission can ever compile
            self._prefill_buckets = tuple(sorted(set(
                prefill_buckets if prefill_buckets is not None
                else default_buckets(max_len)) | {max_len}))
            self._decode = self._jit_entry(
                "decode",
                lambda p, c, t, pos, tbl: decode_step(p, cfg, c, t, pos, tbl,
                                                      attn_impl=attn),
                donate=(1,))
            self._prefill = self._jit_entry(
                "prefill", lambda p, b, lp: prefill(p, cfg, b, logit_pos=lp))
            if spec is not None:
                if not self._all_paged:
                    raise ValueError(
                        "spec= requires every cache leaf behind the block "
                        "table (slot-indexed state — SSM, conv tails, cross "
                        "K/V — cannot be rolled back by table rewind)")
                # the draft is a *view* of the (already converted) serving
                # pool — shared non-linear leaves, re-ranked or strided
                # linears — so drafting adds no weight storage
                dp, dcfg, cache_idx = make_draft(
                    self.params, cfg, kind=spec.draft, stride=spec.stride)
                self._draft_params = dp
                self._draft_cfg = dcfg
                self.draft_stream = weight_stream_bytes(dp, dcfg)
                self._propose = self._jit_entry(
                    "propose",
                    lambda p, c, t, pos, tbl: draft_propose_k(
                        p, dcfg, c, t, pos, tbl, k=spec.k, attn_impl=attn,
                        cache_idx=cache_idx),
                    donate=(1,))
                self._verify = self._jit_entry(
                    "verify",
                    lambda p, c, t, pos, tbl: verify_step(
                        p, cfg, c, t, pos, tbl, attn_impl=attn),
                    donate=(1,))
        else:
            self.pool = None
            self.index = None
            self._all_paged = False
            self._prefill_buckets = ()
            self.caches, cache_specs = init_caches(cfg, n_slots, max_len)
            if mesh is not None:
                self.caches = jax.device_put(self.caches, make_shardings(
                    cache_specs, mesh, self.rules, shapes_tree=self.caches))
            # sequence-axis detection (same structural probe the paged pool
            # uses) so stats() can split true KV bytes from slot-indexed
            # state instead of lumping every leaf into "resident KV"
            _, _, self._slotted_seq_axes, _ = _detect_layout(cfg, n_slots)
            # one jit each: decode re-uses a single (pool-shaped) executable;
            # prefill compiles per distinct prompt length (paged buckets).
            self._decode = self._jit_entry(
                "decode", lambda p, c, t, pos: decode_step(p, cfg, c, t, pos),
                donate=(1,))
            self._prefill = self._jit_entry(
                "prefill", lambda p, b: prefill(p, cfg, b))
        self._exec_shapes = None
        self.prewarmed = False
        self.prewarm_seconds = 0.0
        if prewarm:
            self.prewarm()
        self.init_seconds = time.perf_counter() - t_init
        # anything compiled from here on is a *mid-serve* compile — the
        # cold-start bill prewarm exists to remove (strict mode raises)
        self._compile_log.serving = True

    def _jit_entry(self, name: str, fn, donate=()) -> JitEntry:
        """One accounted jit entry point (see ``serve.prewarm.JitEntry``):
        over a mesh, every trace runs inside the engine's ``axis_rules``
        context so the model's ``constrain`` annotations — and the
        compressed ring's mesh lookup — resolve.  ``donate`` marks argnums
        whose buffers the step may reuse in place — the decode/propose/
        verify cache pools thread linearly through the tick loop, so
        donating them makes every step update the pool without a
        device-side copy of the full KV state.  All entries share the
        engine's ``CompileLog``, so ``stats()`` sees the whole compile
        bill."""
        entry = JitEntry(name, fn, donate=donate, mesh=self.mesh,
                         rules=self.rules, log=self._compile_log)
        self._jits[name] = entry
        return entry

    @property
    def prefill_buckets(self) -> Tuple[int, ...]:
        return self.executable_shapes()["prefill_buckets"]

    def executable_shapes(self) -> Dict[str, object]:
        """The complete compiled-shape universe of this engine config — the
        single source of truth consulted by admission (``_plan`` buckets via
        the ``prefill_buckets`` property), by ``prewarm()`` (what to
        AOT-compile) and by ``stats()`` (``executables_expected``), so what
        we prewarm, what we admit against and what we report cannot drift.

        paged: one pool-shaped executable each for decode (and propose /
        verify under ``spec=``) plus one prefill shape per bucket — the
        bucket set contains ``max_len``, so every admissible prompt lands
        in a bucket (token prompts bucket down, embeds prompts and
        sub-bucket token prompts bucket up) and the set is closed.
        slotted: decode is one executable; prefill compiles per distinct
        prompt length, which no config-only enumeration can bound —
        ``prewarm(prompt_lens=...)`` takes the trace's lengths explicitly."""
        if self._exec_shapes is None:
            entries: Dict[str, int] = {"decode": 1}
            if self.kv == "paged":
                entries["prefill"] = len(self._prefill_buckets)
                if self._spec is not None:
                    entries["propose"] = 1
                    entries["verify"] = 1
            self._exec_shapes = {
                "prefill_buckets": self._prefill_buckets,
                "entries": entries,
                "total": sum(entries.values()),
            }
        return self._exec_shapes

    def prewarm(self, prompt_lens: Sequence[int] = ()) -> None:
        """AOT-compile the engine's complete executable set before any
        request is admitted (``jit(...).lower(abstract).compile()`` per
        shape; see ``serve.prewarm.JitEntry.aot_compile``).  The params and
        cache pools are lowered *concrete* — their committed shardings (the
        TP mesh layout) are baked into the executables — while the per-call
        host arguments (tokens, positions, tables, prompt batches) lower as
        ``ShapeDtypeStruct``s.  Idempotent: shapes already registered are
        skipped.  ``prompt_lens`` adds explicit prefill lengths — the only
        way to prewarm slotted prefill, whose shape set is per-prompt."""
        t0 = time.perf_counter()
        shapes = self.executable_shapes()
        sds = jax.ShapeDtypeStruct
        tok = sds((self.n_slots,), jnp.int32)
        pos = sds((self.n_slots,), jnp.int32)
        if self.kv == "paged":
            caches = self.pool.caches
            tbl = sds((self.n_slots, self.pool.table_width), jnp.int32)
            self._decode.aot_compile(self.params, caches, tok, pos, tbl,
                                     label="decode")
            if self._spec is not None:
                k = self._spec.k
                self._propose.aot_compile(self._draft_params, caches, tok,
                                          pos, tbl, label=f"propose@k{k}")
                span = sds((self.n_slots, k + 1), jnp.int32)
                self._verify.aot_compile(self.params, caches, span, pos, tbl,
                                         label=f"verify@k{k}")
            lens = set(shapes["prefill_buckets"]) | set(prompt_lens)
            for b in sorted(lens):
                self._prefill.aot_compile(
                    self.params, abstract_batch(self.cfg, b),
                    sds((), jnp.int32), label=f"prefill@{b}")
        else:
            self._decode.aot_compile(self.params, self.caches, tok, pos,
                                     label="decode")
            for b in sorted(set(prompt_lens)):
                self._prefill.aot_compile(
                    self.params, abstract_batch(self.cfg, b),
                    label=f"prefill@{b}")
        self.prewarm_seconds += time.perf_counter() - t0
        self.prewarmed = True

    def compile_events(self) -> List[Dict[str, object]]:
        """Per-executable compile records (entry, label, phase, trace/total
        seconds) — the observability feed for the CLI and BENCH_9."""
        return [dataclasses.asdict(e) for e in self._compile_log.events]

    # --------------------------------------------------------------- frontend

    def submit(self, req: Request) -> None:
        """Queue a request.  A request the pool can never serve (span beyond
        ``max_len``, or more blocks than physically exist) is recorded as a
        rejected ``RequestResult`` instead of raising — one oversize request
        must not kill every other in-flight request in the trace."""
        if req.prompt_len + req.max_new_tokens - 1 > self.max_len:
            self._reject(req, f"prompt {req.prompt_len} + gen "
                              f"{req.max_new_tokens} exceeds pool max_len "
                              f"{self.max_len}")
            return
        if self.kv == "paged":
            need = self.pool.blocks_for(req.prompt_len + req.max_new_tokens - 1)
            if need > self.pool.usable_blocks:
                self._reject(req, f"needs {need} blocks, pool has "
                                  f"{self.pool.usable_blocks} usable")
                return
        self.scheduler.submit(req)

    def _reject(self, req: Request, reason: str) -> None:
        self.results[req.rid] = RequestResult(
            rid=req.rid, tokens=np.zeros(0, np.int32), admitted_at=-1,
            finished_at=-1, rejected=True, reason=reason)
        self.rejected += 1

    # ------------------------------------------------------------- admission

    def _plan(self, req: Request) -> "tuple[int, bool]":
        """Bucketed prefill plan for a request: ``(prefill_len, pad_up)``.

        ``pad_up=False`` — prefill the first ``prefill_len`` prompt tokens
        and replay the remainder through forced decode steps (token
        families bucketing DOWN).  ``pad_up=True`` — right-pad the prompt
        to ``prefill_len``, read logits at ``prompt_len - 1``, seed only
        the real positions: embeds prompts always (they cannot replay
        through the token decode step), and token prompts shorter than the
        smallest bucket (nothing to bucket down to; padding is causal-safe,
        so this keeps compiled shapes within the bucket set).  A prompt no
        bucket covers falls back to its exact length."""
        plen = req.prompt_len
        if not self.prefill_buckets:
            return plen, False
        if not self._pads_up():
            downs = [b for b in self.prefill_buckets if b <= plen]
            if downs:
                return max(downs), False
        ups = [b for b in self.prefill_buckets if b >= plen]
        if ups:
            return min(ups), True
        return plen, False

    def _pads_up(self) -> bool:
        # embeds-input prompts cannot be replayed through the token decode
        # step, so they always bucket UP (causal-safe right padding)
        return self.cfg.input_mode == "embeds" and self.cfg.family != "audio"

    def _seed_positions(self, req: Request) -> int:
        """How many prompt positions admission materializes into the cache."""
        pb, pad_up = self._plan(req)
        return req.prompt_len if pad_up else pb

    def _prefix_cacheable(self, req: Request) -> bool:
        """Prefix sharing is keyed on tokens and requires every cache leaf
        to live behind the block table (slot-indexed state — SSM, conv
        tails, encoder cross K/V — is only regenerated by prefill)."""
        return (self.index is not None and self._all_paged
                and set(req.inputs) == {"tokens"})

    def _match(self, req: Request, now: int
               ) -> "tuple[int, List[int], Optional[object]]":
        """Longest cached prefix of ``req``'s prompt: ``(m, blocks, node)``
        where ``blocks`` back positions [0, m) and ``node`` is the deepest
        trie node on the match path (for ``_reclaim``'s eviction pin).
        Capped at ``prompt_len - 1`` — the last prompt token always feeds
        through decode to produce the first logits (they are not cached).

        The per-token pids collapse to one block per ``block_size`` span by
        taking the pid at each span's **last** matched position.  A match
        that crosses a radix-node boundary mid-block (prompts X+A then X+B
        retired with ``len(X) % block_size != 0``) sees two pids inside the
        boundary span: the older branch's block, whose positions past the
        boundary hold *that* branch's KV, and the later branch's
        copy-on-write block, which copied the span before diverging and so
        holds the full history consistent with the matched tokens.  The
        last position's pid is always the latter."""
        if not self._prefix_cacheable(req):
            return 0, [], None
        toks = np.asarray(req.inputs["tokens"])[:req.prompt_len - 1]
        m, pids, node = self.index.match_path(toks, now)
        if m <= 0:
            return 0, [], None
        bs = self.pool.block_size
        return (m, [pids[min(i + bs - 1, m - 1)] for i in range(0, m, bs)],
                node)

    def _fits(self, req: Request, now: int) -> bool:
        """Block-aware admission gate.  A prefix hit shrinks the fresh-block
        need to one (the shared span is a table write; the first divergent
        write needs one block for COW/growth) and **pins its match path**
        while reclaiming — otherwise the eviction loop could drop the very
        nodes that justified the one-block need, and admission's re-match
        would require full-prefill blocks this gate never reserved.  A
        suspended request needs exactly its swapped resident set back.  When
        the free heap is short, LRU-evict the prefix index before refusing —
        cached-but-idle blocks must never starve admission."""
        if req.rid in self._suspended:
            return self._reclaim(
                max(self._suspended[req.rid].swap.n_blocks, 1))
        m, _, node = self._match(req, now)
        if m > 0 and self._reclaim(1, protect=(node,)):
            return True
        # no hit — or the pool is so pinned by the match's own path that one
        # free block cannot be reclaimed around it: fall back to the full-
        # prefill need with nothing protected (admission re-matches and
        # shares whatever smaller hit survives the eviction)
        return self._reclaim(self.pool.blocks_for(self._seed_positions(req)))

    def _reclaim(self, need: int, protect: Sequence = ()) -> bool:
        """Evict LRU prefix-index entries until ``need`` blocks are free (or
        nothing evictable is left); ``protect`` exempts the current
        admission's match path.  True when the allocation can proceed."""
        while not self.pool.can_alloc(need):
            if self.index is None or not self.index.evict_lru(
                    self.pool, protect=protect):
                return False
            self.index_evictions += 1
        return True

    def _admit(self, slot: int, req: Request, now: int) -> bool:
        """Install ``req`` into ``slot``.  Returns False when a paged
        admission backed out (the blocks the fits-gate sized against are
        gone by allocation time): the request is requeued at the queue
        front, the slot freed, and the caller stops admitting this tick.

        The whole admission is the ``serve.admit`` span (count:
        ``positions`` prefilled); a request left replaying its prompt
        through decode ticks starts its ``serve.replay`` span where this
        one ends."""
        with trace.span("serve.admit", engine=self.trace_id,
                        rid=req.rid, positions=0) as sp:
            if self.kv != "paged":
                ok = self._admit_slotted(slot, req, now, sp)
            elif req.rid in self._suspended:
                ok = self._resume(slot, req, now)
            else:
                ok = self._admit_paged(slot, req, now, sp)
        st = self._slots.get(slot)
        if (ok and st is not None and st.req is req and not st.tokens
                and math.isnan(st.replay_from)):
            st.replay_from = sp.end
        return ok

    def _admit_slotted(self, slot: int, req: Request, now: int, sp) -> bool:
        self.prefill_lengths.add(req.prompt_len)
        self.prefill_calls += 1
        sp.counts["positions"] = req.prompt_len
        with trace.span("serve.prefill"):
            batch = {k: jnp.asarray(v)[None] for k, v in req.inputs.items()}
            logits, pf = self._prefill(self.params, batch)
        with trace.span("serve.seed"):
            single, _ = init_caches(self.cfg, 1, self.max_len)
            single = seed_decode_caches(self.cfg, single, pf)
            self.caches = scatter_slot(self.caches, single, slot)
        with trace.span("serve.sync"):
            first = int(jnp.argmax(logits[0]))
        self._slots[slot] = _SlotState(req=req, tokens=[first],
                                       admitted_at=now)
        self.pos[slot] = req.prompt_len
        self.tok[slot] = first
        self.active[slot] = True
        if req.max_new_tokens <= 1:          # satisfied by prefill alone
            self._retire(slot, now)
        return True

    def _admit_paged(self, slot: int, req: Request, now: int, sp) -> bool:
        plen = req.prompt_len
        # prefix-cache hit: the shared span is already resident — point the
        # slot's table at the cached blocks (a table write, zero prefill)
        # and replay only the divergent suffix through forced decode steps.
        # Re-matched here (not reused from _fits) so an eviction between the
        # two calls can never hand out a freed block.
        m, shared, _ = self._match(req, now)
        if m > 0:
            self.pool.share(slot, shared)
            toks = np.asarray(req.inputs["tokens"])
            self._slots[slot] = _SlotState(
                req=req, tokens=[], admitted_at=now,
                pending=[int(t) for t in toks[m + 1:plen]])
            self.pos[slot] = m
            self.tok[slot] = int(toks[m])
            self.active[slot] = True
            self.prefix_hits += 1
            self.prefix_hit_tokens += m
            return True
        pb, pad_up = self._plan(req)
        n_seed = plen if pad_up else pb
        if not self.pool.alloc(slot, self.pool.blocks_for(n_seed)):
            # the fits-gate sized this admission against a state (a prefix
            # match, its pinned path) that no longer holds — back out
            # instead of killing the run: requeue at the queue front and
            # retry once retirements/evictions refill the free heap
            self.scheduler.preempt(slot)
            return False
        sp.counts["positions"] = pb
        with trace.span("serve.prefill"):
            # build the bucketed prefill batch: bucket-down truncates the
            # token prompt (remainder replays through decode), pad-up
            # right-pads the prompt itself (positions >= plen never reach
            # earlier logits and are never seeded; encoder inputs are not
            # positions, keep whole)
            batch = {}
            for k, v in req.inputs.items():
                a = jnp.asarray(v)[None]
                if k == "tokens" and not pad_up:
                    a = a[:, :pb]
                elif pad_up and k != "enc_embeds" and pb > plen:
                    a = jnp.pad(a, ((0, 0), (0, pb - plen))
                                + ((0, 0),) * (a.ndim - 2))
                batch[k] = a
            self.prefill_lengths.add(pb)
            self.prefill_calls += 1
            lp = (plen if pad_up else pb) - 1
            logits, pf = self._prefill(self.params, batch,
                                       jnp.asarray(lp, jnp.int32))
        with trace.span("serve.seed"):
            self.pool.seed(slot, pf, n_seed)
        if n_seed >= plen:                   # prompt fully prefilled
            with trace.span("serve.sync"):
                first = int(jnp.argmax(logits[0]))
            st = _SlotState(req=req, tokens=[first], admitted_at=now)
            self.pos[slot] = plen
            self.tok[slot] = first
        else:                                # catch up via forced decode
            toks = np.asarray(req.inputs["tokens"])
            st = _SlotState(req=req, tokens=[], admitted_at=now,
                            pending=[int(t) for t in toks[pb + 1:plen]])
            self.pos[slot] = pb
            self.tok[slot] = int(toks[pb])
        self._slots[slot] = st
        self.active[slot] = True
        if st.tokens and req.max_new_tokens <= 1:
            self._retire(slot, now)
        return True

    def _retire(self, slot: int, now: int) -> None:
        st = self._slots.pop(slot)
        self.results[st.req.rid] = RequestResult(
            rid=st.req.rid, tokens=np.asarray(st.tokens, np.int32),
            admitted_at=st.admitted_at, finished_at=now)
        self.scheduler.release(slot)
        self.active[slot] = False
        if self.kv == "paged":
            if self._prefix_cacheable(st.req):
                # register the prompt's (token -> block) mapping BEFORE the
                # slot releases its references: the index pins the blocks,
                # so the cached span never transits through the free heap
                toks = np.asarray(st.req.inputs["tokens"])[:st.req.prompt_len]
                bs = self.pool.block_size
                pids = [int(self.pool.table[slot, i // bs])
                        for i in range(len(toks))]
                self.index.insert(toks, pids, now, self.pool)
            self.pool.free(slot)
            self.pos[slot] = 0               # idle rows write into trash:0
            self.tok[slot] = 0

    # ------------------------------------------------------------ preemption

    def _preempt(self, slot: int, now: int) -> None:
        """Evict ``slot`` back to the queue front.  ``preempt="replay"``
        throws the resident state away (readmission replays from prefill);
        ``preempt="suspend"`` swaps it to host numpy — blocks, slot-indexed
        state, emitted tokens, prompt catch-up — and readmission restores
        it, so the cost scales with resident bytes, not prompt length."""
        st = self._slots.pop(slot)
        if self.preempt_mode == "suspend":
            self._suspended[st.req.rid] = _Suspended(
                state=st, swap=self.pool.swap_out(slot),
                pos=int(self.pos[slot]), tok=int(self.tok[slot]))
            self.swap_outs += 1
            self.scheduler.suspend(slot)     # requeued at the FRONT, tagged
        else:
            self.pool.free(slot)
            self.scheduler.preempt(slot)     # requeued at the FRONT
        self.active[slot] = False
        self.pos[slot] = 0
        self.tok[slot] = 0
        self.preemptions += 1

    def _resume(self, slot: int, req: Request, now: int) -> bool:
        """Re-admit a suspended request: swap its resident state back in and
        continue exactly where it stopped — no prefill, no token replay.
        Backs out (False: re-suspended at the queue front) if the pool
        cannot back the swapped blocks despite the fits-gate."""
        sus = self._suspended.pop(req.rid)
        if not self.pool.swap_in(slot, sus.swap):
            self._suspended[req.rid] = sus
            self.scheduler.suspend(slot)
            return False
        self._slots[slot] = sus.state
        self.pos[slot] = sus.pos
        self.tok[slot] = sus.tok
        self.active[slot] = True
        self.swap_ins += 1
        return True

    def _prepare_slots(self, now: int, spec_set: Optional[set] = None) -> None:
        """Make every active slot writable for this tick: lazily back its
        write span (``ensure``) and copy-on-write every shared block the
        span touches (``cow`` — a shared block is never mutated).  A slot in
        ``spec_set`` writes a k+1-wide speculative span this tick, so its
        whole span must be backed and exclusive up front — that exclusivity
        is what lets ``BlockPool.rollback`` free rejected-tail blocks
        without consulting anyone else's table.  When the pool runs dry,
        reclaim LRU prefix-index blocks first, then *demote* the slot from
        speculation (a 1-wide span needs fewer blocks) before preempting
        the newest-admitted request (oldest requests are never preempted,
        so progress is guaranteed)."""
        k = self._spec.k if self._spec is not None else 0
        bs = self.pool.block_size
        for slot in sorted(self._slots,
                           key=lambda s: (self._slots[s].admitted_at, s)):
            while slot in self._slots:       # not preempted by earlier victim
                pos = int(self.pos[slot])
                spec = spec_set is not None and slot in spec_set
                last = pos + (k if spec else 0)
                owned = self.pool._owned[slot]
                short = max(0, last // bs + 1 - len(owned))
                shared = [i for i in range(pos // bs,
                                           min(last // bs, len(owned) - 1) + 1)
                          if self.pool.ref[self.pool.table[slot, i]] > 1]
                ok = (self._reclaim(short + len(shared))
                      and self.pool.ensure(slot, last))
                for i in shared:
                    ok = ok and self.pool.cow(slot, i * bs)
                if ok:
                    break
                if spec:                     # cheapen before evicting anyone
                    spec_set.discard(slot)
                    continue
                victim = max(self._slots,
                             key=lambda s: (self._slots[s].admitted_at, s))
                self._preempt(victim, now)

    # ----------------------------------------------------------------- decode

    def step(self, now: int) -> None:
        """One batched decode tick over the pool (per-slot positions).

        The tick is the ``serve.step`` span (counts: ``rows`` decoded; on
        the plain paged tick also ``kv_blocks``, the table columns the fused
        attention kernel computes per layer, ``ceil((pos + 1) / block_size)``
        summed over every slot, idle ones included, and ``table_blocks``,
        the columns its grid spans, slots times table width) and runs in
        three phases, each a child span: ``serve.prepare`` backs
        and copies-on-write every active slot's write block and builds the
        inputs; ``serve.sync`` dispatches the decode and reads its tokens
        back, the host blocked on the device; ``serve.commit`` appends the
        tokens and retires finished requests.

        Occupancy is sampled HERE, after ``_prepare_slots`` has run its
        preemptions and only when a decode step actually executes — sampling
        before (as ``run`` once did) recorded phantom active slots on ticks
        whose slots all got preempted and counted ticks that decoded
        nothing."""
        with trace.span("serve.step", engine=self.trace_id, rows=0) as sp:
            if self._spec is not None:
                self._spec_step(now, sp)
            else:
                self._decode_tick(now, sp)

    def _decode_tick(self, now: int, sp) -> None:
        paged = self.kv == "paged"
        with trace.span("serve.prepare"):
            if paged:
                self._prepare_slots(now)
                if not self._slots:
                    return                   # everything was preempted
            self.scheduler.record_occupancy()
            if paged and self.debug_invariants:
                # the fused kernel reads exactly the blocks the table names:
                # prove every active slot's read window is backed by owned,
                # non-free, non-trash blocks — and its write block exclusive
                # (COW ran) — before launching it
                self.check_invariants(
                    active_pos={s: int(self.pos[s]) for s in self._slots})
            args = (jnp.asarray(self.tok), jnp.asarray(self.pos))
            if paged:
                args += (self.pool.device_table(),)
        sp.counts["rows"] = len(self._slots)
        if paged:
            bs = self.pool.block_size
            sp.counts["kv_blocks"] = int(((self.pos + bs) // bs).sum())
            sp.counts["table_blocks"] = self.n_slots * self.pool.table_width
        with trace.span("serve.sync") as sync:
            if paged:
                logits, self.pool.caches = self._decode(
                    self.params, self.pool.caches, *args)
            else:
                logits, self.caches = self._decode(
                    self.params, self.caches, *args)
            nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.decode_steps += 1
        with trace.span("serve.commit"):
            for slot in list(self._slots):
                self._advance(slot, int(nxt[slot]), now, sync.end)

    def _advance(self, slot: int, token: int, now: int, t: float) -> None:
        """Move ``slot`` one position on after a tick fed it ``self.tok``:
        while its prompt replays it takes the next prompt token, otherwise
        it emits ``token`` (on the host since ``t``)."""
        st = self._slots[slot]
        self.pos[slot] += 1
        if not st.tokens:                    # the tick fed a prompt token
            self._fed_prompt(st, 1)
        if st.pending:                       # still consuming the prompt
            self.tok[slot] = st.pending.pop(0)
            return
        st.tokens.append(token)
        self.tok[slot] = token
        if len(st.tokens) == 1:
            self._replay_done(st, t)
        if len(st.tokens) >= st.req.max_new_tokens:
            self._retire(slot, now)

    def _fed_prompt(self, st: _SlotState, n: int) -> None:
        st.replayed += n
        self.replayed_prompt_tokens += n

    def _replay_done(self, st: _SlotState, t: float) -> None:
        """The request's first token came from a decode tick: record its
        prompt replay, admission's end to ``t``, as ``serve.replay``."""
        if st.replayed and not math.isnan(st.replay_from):
            trace.record("serve.replay", st.replay_from, t,
                         engine=self.trace_id, rid=st.req.rid,
                         tokens=st.replayed)

    # -------------------------------------------------- speculative decoding

    def _masked(self, participants) -> Tuple[jnp.ndarray, ...]:
        """(tok, pos, table) device args with every non-participant row
        pointed at the trash block at position 0 — the same disguise idle
        slots already wear, so a forward over the masked args touches only
        the participants' blocks (non-participant writes land in trash,
        their garbage logits are never read)."""
        tbl = self.pool.table.copy()
        pos = self.pos.copy()
        tok = self.tok.copy()
        for s in range(self.n_slots):
            if s not in participants:
                tbl[s, :] = TRASH_BLOCK
                pos[s] = 0
                tok[s] = 0
        return jnp.asarray(tok), jnp.asarray(pos), jnp.asarray(tbl)

    def _spec_step(self, now: int, sp) -> None:
        """One speculative tick: a plain decode forward for the
        non-speculating slots, then one draft-propose + target-verify round
        for the speculating ones — each forward runs over the full pool
        with the other group's rows masked to trash.

        A slot joins the verify span when its request opted in (``Request
        .spec`` overriding ``SpecConfig.default_on``), its k+1 span fits
        the table, and either it is replaying prompt tokens (``pending``,
        from bucketed-down prefill or a prefix-cache hit) — those are
        *forced* inputs with guaranteed acceptance, so the span consumes up
        to k+1 of them per target pass — or it is generating with at least
        2 tokens of budget left (a 1-token tail gains nothing from a
        verify).  For generating slots the draft proposes k tokens and
        greedy acceptance commits the longest draft prefix matching the
        target's argmax plus the target's token at the first mismatch, so
        every committed token is exactly what the non-speculative oracle
        would have emitted.  Either way the table then rolls back to the
        consumed position, freeing span blocks past it."""
        k = self._spec.k
        cap = self.pool.table_width * self.pool.block_size
        with trace.span("serve.prepare"):
            draft_set, forced_set = set(), set()
            for slot, st in self._slots.items():
                on = (st.req.spec if st.req.spec is not None
                      else self._spec.default_on)
                if not on or int(self.pos[slot]) + k >= cap:
                    continue
                if st.pending:
                    forced_set.add(slot)
                elif st.req.max_new_tokens - len(st.tokens) >= 2:
                    draft_set.add(slot)
            spec_set = draft_set | forced_set
            self._prepare_slots(now, spec_set)
            if not self._slots:
                return                       # everything was preempted
            spec_set &= set(self._slots)
            draft_set &= spec_set
            forced_set &= spec_set
            plain = set(self._slots) - spec_set
            if self.debug_invariants:
                self.check_invariants(active_pos={
                    s: int(self.pos[s]) + (k if s in spec_set else 0)
                    for s in self._slots})
        sp.counts["rows"] = len(self._slots)
        if plain:
            tok, pos, tbl = self._masked(plain)
            with trace.span("serve.sync") as sync:
                logits, self.pool.caches = self._decode(
                    self.params, self.pool.caches, tok, pos, tbl)
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
            self.decode_steps += 1
            self.scheduler.record_occupancy()
            with trace.span("serve.commit"):
                for slot in sorted(plain):
                    self._advance(slot, int(nxt[slot]), now, sync.end)
        if not spec_set:
            return
        span = np.zeros((self.n_slots, k + 1), np.int32)
        span[:, 0] = self.tok
        if draft_set:
            tok, pos, tbl = self._masked(draft_set)
            with trace.span("serve.sync"):
                drafts, self.pool.caches = self._propose(
                    self._draft_params, self.pool.caches, tok, pos, tbl)
                span[:, 1:] = np.asarray(drafts, np.int32)
            self.draft_steps += k
        # forced rows: the next prompt tokens ride the span in place of
        # drafts — acceptance is structural (the oracle consumes them
        # verbatim), so prompt catch-up advances k+1 positions per pass
        for slot in forced_set:
            pend = self._slots[slot].pending
            f = min(k, len(pend))
            span[slot, 1:] = 0
            span[slot, 1:1 + f] = pend[:f]
        tok, pos, tbl = self._masked(spec_set)
        with trace.span("serve.sync") as sync:
            vlogits, self.pool.caches = self._verify(
                self.params, self.pool.caches, jnp.asarray(span), pos, tbl)
            va = np.asarray(jnp.argmax(vlogits, axis=-1), np.int32)  # [B,k+1]
        self.decode_steps += 1
        self.scheduler.record_occupancy()
        acc = accept_greedy(span[:, 1:], va)
        with trace.span("serve.commit"):
            for slot in sorted(spec_set):
                self._commit_span(slot, slot in forced_set, va[slot],
                                  int(acc[slot]), now, sync.end)

    def _commit_span(self, slot: int, forced: bool, va, acc: int, now: int,
                     t: float) -> None:
        """Commit one verified k+1 span of ``slot``: ``va`` is the target's
        argmax at each span position, ``acc`` the drafts it accepted."""
        k = self._spec.k
        st = self._slots[slot]
        if forced:
            f = min(k, len(st.pending))
            del st.pending[:f]
            self.pos[slot] += f + 1
            self._fed_prompt(st, f + 1)
            if st.pending:                   # prompt not done: no emission
                self.tok[slot] = st.pending.pop(0)
            else:                            # first post-prompt emission
                st.tokens.append(int(va[f]))
                self.tok[slot] = int(va[f])
                self._replay_done(st, t)
            self.steps_saved += f
        else:
            budget = st.req.max_new_tokens - len(st.tokens)
            n_commit = min(acc + 1, budget)
            commit = [int(x) for x in va[:n_commit]]
            if not st.tokens:                # the span began on the prompt's
                self._fed_prompt(st, 1)      # last token: replay is over
                self._replay_done(st, t)
            st.tokens.extend(commit)
            self.pos[slot] += n_commit
            self.tok[slot] = commit[-1]
            self.spec_proposed += k
            self.spec_accepted += acc
            self.steps_saved += n_commit - 1
        # rewind: keep blocks backing the consumed positions, free the
        # span tail (exclusive by _prepare_slots, so this can never
        # take a block out from under another table)
        self.pool.rollback(slot, int(self.pos[slot]))
        if st.tokens and len(st.tokens) >= st.req.max_new_tokens:
            self._retire(slot, now)

    # -------------------------------------------------------------- main loop

    def run(self, requests: Optional[List[Request]] = None
            ) -> Dict[int, RequestResult]:
        """Drive to completion: admit-then-step once per tick."""
        for r in requests or ():
            self.submit(r)
        t = 0
        while self.scheduler.has_work():
            if self.kv == "paged":
                # one at a time: each admission allocates blocks, and the
                # next fits-check must see the shrunken free list
                while True:
                    pairs = self.scheduler.admit(
                        t, fits=lambda r: self._fits(r, t), limit=1)
                    if not pairs:
                        break
                    if not self._admit(pairs[0][0], pairs[0][1], t):
                        break                # backed out: retry next tick
            else:
                for slot, req in self.scheduler.admit(t):
                    self._admit(slot, req, t)
            if self.active.any():
                self.step(t)                 # samples occupancy iff it decodes
            t += 1
        self.ticks = t
        return self.results

    def check_invariants(self, active_pos: Optional[Dict[int, int]] = None
                         ) -> None:
        """Pool invariants with the engine's full reference picture: the
        prefix index's block pins ride along as ``external_refs`` so the
        free-XOR-refcounted accounting closes."""
        self.pool.check_invariants(
            active_pos=active_pos,
            external_refs=self.index.block_refs() if self.index else None)

    def stats(self) -> Dict[str, float]:
        toks = sum(len(r.tokens) for r in self.results.values())
        ws = self.weight_stream
        log = self._compile_log
        ticks = [s.dur for s in trace.spans(engine=self.trace_id,
                                             name="serve.step")]
        out = {"decode_steps": float(self.decode_steps),
               "occupancy": self.scheduler.occupancy(),
               "tokens": float(toks),
               "ticks": float(self.ticks),
               # the full compile bill, per entry point: executables
               # actually built (prewarmed + lazy), not just the prefill
               # lengths admission asked for — decode/propose/verify were
               # previously invisible here
               "prefill_compiles": float(self._prefill.n_compiles),
               "decode_compiles": float(self._decode.n_compiles),
               "prewarmed_executables": float(log.prewarm_compiles),
               "mid_serve_compiles": float(log.mid_serve_compiles),
               "compile_seconds": float(log.compile_seconds),
               "prewarm_seconds": float(self.prewarm_seconds),
               "init_seconds": float(self.init_seconds),
               "warm_calls": float(sum(j.warm_calls
                                       for j in self._jits.values())),
               "executables_expected": float(
                   self.executable_shapes()["total"]),
               # wall time of this engine's decode ticks (serve.step spans;
               # a tick reads its tokens back, so each is synchronous)
               "first_tick_s": float(ticks[0] if ticks else 0.0),
               "steady_tick_s": float(np.median(ticks[1:])
                                      if len(ticks) > 1 else 0.0),
               "replayed_prompt_tokens": float(self.replayed_prompt_tokens),
               "prefill_calls": float(self.prefill_calls),
               "rejected": float(self.rejected),
               # per-decode-step weight-stream traffic (every step re-reads
               # each linear once; see models.weight_stream_bytes)
               "weight_stream_bytes": float(ws["stream_bytes"]),
               "dense_weight_bytes": float(ws["dense_bytes"]),
               "weight_stream_ratio": float(ws["ratio"]),
               "tp": float(self.mesh.shape["model"]) if self.mesh else 1.0}
        if self.ring_traffic is not None:
            rt = self.ring_traffic
            # modeled per-decode-step interconnect traffic (see
            # models.serve_ring_traffic_bytes): what the ring ships
            # compressed vs the same ring shipping dense weights
            out.update({
                "ring_bytes_per_step": float(rt["ring_bytes"]),
                "ring_dense_bytes_per_step": float(rt["dense_ring_bytes"]),
                "ring_traffic_ratio": float(rt["ratio"]),
                "ring_linears": float(rt["ring_linears"]),
                "local_linears": float(rt["local_linears"])})
        if self.kv == "paged":
            out.update({
                "preemptions": float(self.preemptions),
                "kv_block_bytes": float(self.pool.bytes_per_block),
                "kv_bytes_resident": float(self.pool.resident_bytes()),
                "kv_bytes_peak": float(self.pool.peak_blocks
                                       * self.pool.bytes_per_block),
                "kv_bytes_capacity": float(self.pool.usable_blocks
                                           * self.pool.bytes_per_block),
                "kv_state_bytes": float(self.pool.state_bytes),
                "prefix_hits": float(self.prefix_hits),
                "prefix_hit_tokens": float(self.prefix_hit_tokens),
                "cow_copies": float(self.pool.cow_copies),
                "swap_outs": float(self.swap_outs),
                "swap_ins": float(self.swap_ins),
                "swap_bytes_resident": float(sum(
                    s.swap.nbytes for s in self._suspended.values())),
                "index_evictions": float(self.index_evictions),
                "index_blocks": float(self.index.blocks if self.index else 0),
                "index_tokens": float(self.index.cached_tokens
                                      if self.index else 0)})
            if self._spec is not None:
                # acceptance = share of drafted tokens the target kept;
                # steps_saved = target passes the oracle would have needed
                # beyond what speculation actually ran
                out.update({
                    "propose_compiles": float(self._propose.n_compiles),
                    "verify_compiles": float(self._verify.n_compiles),
                    "spec_proposed": float(self.spec_proposed),
                    "spec_accepted": float(self.spec_accepted),
                    "spec_acceptance": (self.spec_accepted
                                        / max(self.spec_proposed, 1)),
                    "spec_steps_saved": float(self.steps_saved),
                    "draft_steps": float(self.draft_steps),
                    # per-draft-step weight-stream bytes of the draft *view*
                    # (shared storage with the target pool; this is the
                    # modeled read share, not extra resident bytes)
                    "draft_stream_bytes": float(
                        self.draft_stream["stream_bytes"])})
        else:
            # sequence-axis leaves are the KV stream; slot-indexed state
            # (SSM state, conv tails, encoder cross K/V) reports separately,
            # mirroring the paged split so BENCH comparisons are
            # apples-to-apples (the slotted layout preallocates every row,
            # so resident == capacity by construction)
            leaves = jax.tree_util.tree_leaves(self.caches)
            out["kv_bytes_resident"] = float(sum(
                l.nbytes for l, ax in zip(leaves, self._slotted_seq_axes)
                if ax is not None))
            out["kv_state_bytes"] = float(sum(
                l.nbytes for l, ax in zip(leaves, self._slotted_seq_axes)
                if ax is None))
        return out
