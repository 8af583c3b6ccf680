"""Compile management for the serve engine: persistent cache, AOT prewarm,
and cold/warm compile observability.

The paper's whole argument is that decode-time matmul cost is dominated by
per-iteration overhead you can hoist out of the loop (index setup, loop
structure, the vindexmac instruction doing the index resolution once).  The
serving analogue of that overhead is XLA tracing + compilation: every
prefill bucket, every (plain, k+1-span) decode/propose/verify shape and
every TP mesh variant traces and compiles its own executable, and paying
that lazily at first use turns cold start into minutes of XLA time on big
configs.  This module moves all of it out of the serving loop:

* ``enable_compile_cache`` turns on ``jax``'s persistent compilation cache:
  in ``$JAX_COMPILATION_CACHE_DIR`` where that is set, else in the repo's
  ``.cache/xla`` — so every executable an engine ever built is reusable
  across process restarts (and warmable in CI).

* ``JitEntry`` wraps one engine jit entry point (decode / prefill / propose
  / verify).  ``aot_compile`` lowers and compiles an abstract shape ahead of
  time (``jit(fn).lower(*abstract).compile()``) and **keeps the compiled
  executable**: a later call with matching shapes dispatches straight to it
  — zero tracing in the steady state.  (Calling the jitted function after an
  AOT compile would still re-trace: ``lower().compile()`` does not populate
  the jit dispatch cache, so the dispatch table here is what actually makes
  prewarmed ticks trace-free.)  Calls that miss the table fall back to the
  ordinary jit path and are *accounted*: a growth of the jit cache is a
  compile event with its wall seconds, tagged ``init`` or ``serve`` by when
  it happened.

* ``CompileLog`` is the engine-wide ledger of those events.  ``strict=True``
  turns any ``serve``-phase compile into a hard ``RuntimeError`` — the
  test-mode assertion that a prewarmed engine's steady state never compiles
  (``mid_serve_compiles == 0``).

* ``abstract_batch`` builds the abstract (ShapeDtypeStruct) prefill batch
  for one bucket, shaped exactly as ``serve.request.synthetic_request``
  builds concrete prompts — one builder for traces and prewarm, so the
  enumerated shape set cannot drift from what admission actually feeds the
  prefill jit.

The shape *enumeration* itself lives on the engine
(``ServeEngine.executable_shapes``) because it is a function of the engine
config: prefill buckets, pool width, spec k, attention impl, mesh.
"""

from __future__ import annotations

import dataclasses
import os
import time
from contextlib import nullcontext
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# anchored on this file, not the working directory: the cache path is part
# of what makes an entry found again, so it must not move with the cwd
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".cache", "xla")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache and return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, jax has already read it as
    its cache directory, and no directory is set here; otherwise the cache
    lives in ``.cache/xla`` at the repository root (git-ignored, and what
    CI persists with ``actions/cache``).

    The min-compile-time / min-entry-size gates are disabled: the serve
    jits on smoke configs compile in well under the default 1 s threshold,
    which would skip exactly the executables prewarm wants to persist.
    Safe to call repeatedly (jax config updates are idempotent)."""
    path = os.environ.get(CACHE_ENV_VAR)
    if not path:
        path = os.path.normpath(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


@dataclasses.dataclass
class CompileEvent:
    """One executable built: where, when, and what it cost.

    ``phase`` is ``"prewarm"`` (AOT at engine init), ``"init"`` (a lazy
    compile before the engine started serving) or ``"serve"`` (a lazy
    compile inside the serving loop — the cold-start bill prewarm exists to
    remove).  ``seconds`` is trace + compile wall time; for fallback (non-
    AOT) compiles it necessarily includes the first execution, which is
    negligible next to XLA compilation.  ``trace_seconds`` is the lowering
    share, known only on the AOT path (0.0 otherwise)."""

    entry: str
    label: str
    phase: str
    seconds: float
    trace_seconds: float = 0.0


class CompileLog:
    """Engine-wide ledger of compile events across every jit entry point.

    ``serving`` flips to True when the engine finishes init/prewarm; any
    event recorded after that is a *mid-serve* compile.  With ``strict``
    set, a mid-serve compile raises instead of merely counting — the hard
    ``mid_serve_compiles == 0`` assertion mode the prewarm tests run
    under."""

    def __init__(self, strict: bool = False):
        self.events: List[CompileEvent] = []
        self.serving = False
        self.strict = strict

    def record(self, ev: CompileEvent) -> None:
        self.events.append(ev)
        if ev.phase == "serve" and self.strict:
            raise RuntimeError(
                f"mid-serve compile of {ev.entry}[{ev.label}] "
                f"({ev.seconds:.3f}s) — the prewarmed executable set does "
                f"not cover this shape; extend "
                f"ServeEngine.executable_shapes()/prewarm() or serve "
                f"without strict_prewarm")

    @property
    def mid_serve_compiles(self) -> int:
        return sum(1 for e in self.events if e.phase == "serve")

    @property
    def prewarm_compiles(self) -> int:
        return sum(1 for e in self.events if e.phase == "prewarm")

    @property
    def compile_seconds(self) -> float:
        return sum(e.seconds for e in self.events)


def _shape_key(args) -> Tuple:
    """Dispatch key of a call: tree structure + per-leaf (shape, dtype).

    Works uniformly over concrete arrays (jnp/np) and ShapeDtypeStructs, so
    the key of ``aot_compile``'s abstract arguments equals the key of the
    live call with matching shapes.  Dict leaves flatten in sorted-key
    order, so prompt-dict insertion order cannot split the cache."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple((tuple(l.shape), np.dtype(l.dtype).name)
                          for l in leaves)


def _describe(args, limit: int = 5) -> str:
    """Short human label for a fallback compile: the trailing leaf shapes
    (the per-call arguments — the big params/cache trees lead)."""
    leaves = jax.tree_util.tree_leaves(args)
    tail = leaves[-limit:]
    return ",".join(f"{np.dtype(l.dtype).name}{list(l.shape)}" for l in tail)


class JitEntry:
    """One engine jit entry point with AOT prewarm and compile accounting.

    Callable like the jitted function.  Dispatch order:

    1. the AOT table — shapes ``aot_compile`` built dispatch directly to
       the stored compiled executable (no tracing, no jit-cache lookup);
    2. the ordinary jit path — and if the jit cache grew across the call
       (``_cache_size``; first-seen shape key when that private probe is
       unavailable), the compile is recorded in the shared ``CompileLog``.

    Over a mesh, both AOT lowering and fallback calls run inside the
    engine's ``axis_rules`` context so the model's ``constrain``
    annotations — and the compressed ring's mesh lookup — resolve.
    ``donate`` marks argnums whose buffers the step may reuse in place
    (the decode/propose/verify cache pools thread linearly through the
    tick loop); the AOT executables honor it identically."""

    def __init__(self, name: str, fn: Callable, donate: Tuple[int, ...] = (),
                 mesh=None, rules=None, log: Optional[CompileLog] = None):
        self.name = name
        self.mesh = mesh
        self.rules = rules
        self.log = log if log is not None else CompileLog()
        self._jf = jax.jit(fn, donate_argnums=donate)
        self._aot: Dict[Tuple, object] = {}
        self._seen: set = set()
        self.n_compiles = 0                  # executables built (AOT + lazy)
        self.warm_calls = 0                  # dispatches that compiled nothing

    def _ctx(self):
        if self.mesh is None:
            return nullcontext()
        from repro.dist.api import axis_rules
        return axis_rules(self.mesh, self.rules)

    def _cache_size(self) -> Optional[int]:
        probe = getattr(self._jf, "_cache_size", None)
        return probe() if probe is not None else None

    def aot_compile(self, *args, label: str = "") -> bool:
        """Lower + compile ``args``'s shape ahead of time and register the
        executable for direct dispatch.  ``args`` may mix concrete arrays
        (params / cache pools — their committed shardings are baked into
        the executable) with ``ShapeDtypeStruct``s for the per-call host
        arguments.  Returns False when the shape is already registered.
        The persistent compilation cache (``enable_compile_cache``) makes
        the ``compile()`` step a disk hit on warm bring-up; lowering always
        runs, which is why warm start is fast but not free."""
        key = _shape_key(args)
        if key in self._aot:
            return False
        t0 = time.perf_counter()
        with self._ctx():
            lowered = self._jf.lower(*args)
        t1 = time.perf_counter()
        self._aot[key] = lowered.compile()
        self.n_compiles += 1
        self.log.record(CompileEvent(
            entry=self.name, label=label or _describe(args), phase="prewarm",
            seconds=time.perf_counter() - t0, trace_seconds=t1 - t0))
        return True

    def __call__(self, *args):
        key = _shape_key(args)
        comp = self._aot.get(key)
        if comp is not None:
            self.warm_calls += 1
            return comp(*args)
        before = self._cache_size()
        t0 = time.perf_counter()
        with self._ctx():
            out = self._jf(*args)
        dt = time.perf_counter() - t0
        after = self._cache_size()
        compiled = (after > before if before is not None
                    else key not in self._seen)
        self._seen.add(key)
        if compiled:
            self.n_compiles += 1
            self.log.record(CompileEvent(
                entry=self.name, label=_describe(args),
                phase="serve" if self.log.serving else "init", seconds=dt))
        else:
            self.warm_calls += 1
        return out


def abstract_batch(cfg, prefill_len: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract [1, L]-batched prefill inputs for one bucket.

    Built from the same family-shaped prompt builder the traces use
    (``serve.request.synthetic_request``), then batched exactly as the
    engine batches real inputs — so the enumerated prefill shapes are the
    shapes admission compiles, by construction: bucket-down truncates the
    token prompt to the bucket, bucket-up right-pads it, and either way the
    leaf that reaches the jit is ``[1, bucket]`` (encoder inputs keep their
    fixed ``[1, enc_seq, d]`` shape)."""
    from repro.serve.request import synthetic_request
    req = synthetic_request(cfg, np.random.default_rng(0), rid=-1,
                            prompt_len=prefill_len, max_new_tokens=1)
    return {k: jax.ShapeDtypeStruct((1,) + v.shape, v.dtype)
            for k, v in req.inputs.items()}
