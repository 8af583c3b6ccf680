"""Continuous-batching serving subsystem for compressed N:M models.

Design
------
Serving is organized around a fixed pool of decode **slots** (batch rows of
one preallocated KV-cache tree) fed by a FCFS request queue:

* ``request``   — ``Request``/``RequestResult`` plus synthetic trace makers.
  Time is counted in scheduler ticks (one batched decode step per tick), so
  traces replay deterministically.
* ``scheduler`` — ``SlotScheduler``: admits queued requests into freed slots
  the tick after the previous occupant emits its last token (continuous
  batching), and records slot-occupancy statistics.
* ``cache``     — the slotted KV-cache pool: ``seed_decode_caches`` copies
  prefill caches into decode buffers (length-clipped per family), and
  ``scatter_slot`` writes a batch-1 cache into one pool slot, locating the
  slot axis structurally so a single admission path covers every family's
  cache layout (dense, local/global, MLA, ssm, hybrid, moe, audio).
* ``paged``     — ``BlockPool``: the paged KV-cache pool.  Attention leaves
  become ``[..., n_blocks, block_size, ...]`` block pools addressed through
  per-request int32 block tables (``table[slot, pos // block_size]``) — the
  software analog of the paper's indexed register reads — so cache memory is
  admitted in blocks instead of whole ``max_len`` rows.  Blocks are
  refcounted (``share`` + copy-on-write) so many tables can name one
  physical block, and ``swap_out``/``swap_in`` round-trip a slot's resident
  state to host numpy for suspend-to-host preemption.
* ``prefix``    — ``PrefixIndex``: host-side radix trie over retired
  prompts' per-token block ids; admission matches incoming prompts against
  it and a hit shares the resident blocks instead of prefilling the shared
  span (LRU leaf eviction under pool pressure).
* ``engine``    — ``ServeEngine``: prefill-on-admission + one batched
  ``decode_step`` per tick with a per-slot int32 position vector (the
  attention caches update and mask per batch row).  ``kv="paged"`` routes
  decode through the block table, buckets prefill lengths to a fixed set of
  compiled shapes, appends blocks lazily, and preempts-to-queue when the
  pool runs dry; ``kv="slotted"`` is the oracle layout.
* ``sequential``— the fixed-batch oracle: the whole batch decodes in
  lockstep until its slowest member finishes.  Continuous batching must be
  token-for-token equivalent to it under matched batch composition; the
  throughput win is purely from refilling early-finished slots.
* ``prewarm``   — compile management: ``enable_compile_cache`` wires jax's
  persistent compilation cache to a repo-local directory (executables
  survive process restarts), and ``JitEntry``/``CompileLog`` give every
  engine jit entry point AOT prewarming (``ServeEngine(prewarm=True)``
  compiles the complete ``executable_shapes()`` set before admission, so
  steady-state ticks never trace) plus per-executable compile accounting
  (``stats()["mid_serve_compiles"]`` et al., hard-asserted zero under
  ``strict_prewarm=True``).
* ``trace``     — the engine's host spans: every decode tick, admission and
  prompt replay, with its phases, in a bounded in-process ring and as
  ``jax.profiler.TraceAnnotation``s on the device trace's clock.

Relation to the paper
---------------------
Decode is the regime the compressed N:M format is built for: each step
streams the compressed weights (values at N/M density + ceil(log2 M)-bit
indices) through a small-batch matvec — ``kernels.nm_spmv``'s vindexmac
dataflow, where every indirect access stays local to the resident activation
tile (companion paper arXiv:2311.07241 shows the same dataflow sustains
decode-shaped matvecs).  The weight stream is re-read once per decode step
regardless of how many slots do useful work, so slot occupancy is exactly
the token yield per compressed-weight pass; the scheduler's job is keeping
that ratio at 1.
"""

from repro.serve.cache import scatter_slot, seed_decode_caches
from repro.serve.engine import ServeEngine
from repro.serve.paged import BlockPool, SwapState, default_buckets
from repro.serve.prefix import PrefixIndex
from repro.serve.prewarm import (CompileEvent, CompileLog, JitEntry,
                                 abstract_batch, enable_compile_cache)
from repro.serve.request import (Request, RequestResult, shared_prefix_trace,
                                 synthetic_request, synthetic_trace)
from repro.serve.scheduler import SlotScheduler
from repro.serve.sequential import serve_fixed_batch, serve_sequential
from repro.serve.speculative import SpecConfig

__all__ = [
    "BlockPool", "CompileEvent", "CompileLog", "JitEntry", "PrefixIndex",
    "Request", "RequestResult", "ServeEngine", "SlotScheduler", "SpecConfig",
    "SwapState", "abstract_batch", "default_buckets", "enable_compile_cache",
    "scatter_slot", "seed_decode_caches", "serve_fixed_batch",
    "serve_sequential", "shared_prefix_trace", "synthetic_request",
    "synthetic_trace",
]
