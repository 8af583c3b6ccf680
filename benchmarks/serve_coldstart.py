"""Cold start vs prewarmed vs cache-warm bring-up of the serve engine.

The paper amortizes per-iteration overhead out of the steady-state loop
(index setup hoisted by vindexmac); the serving analogue is XLA tracing +
compilation, which the lazy engine pays mid-serve at first use of every
shape.  ``ServeEngine(prewarm=True)`` AOT-compiles the complete
``executable_shapes()`` set at init, and ``enable_compile_cache`` persists
the executables across process restarts — so the claims to measure are:

* a prewarmed engine serves the whole trace with **zero mid-serve
  compiles** (its first tick is as fast as its steady tick), emitting
  tokens identical to the lazy engine;
* a **warm** bring-up (second process, same cache dir) is strictly faster
  than the **cold** one (fresh cache dir), because every ``compile()`` is
  a disk hit.

Three bring-ups per arch, each in a fresh subprocess so process state is
honestly cold (in-process jit caches cannot leak between measurements —
a restart is exactly the regime cold start lives in):

* ``lazy``  — no prewarm, fresh cache dir: the baseline compile bill,
  paid mid-serve (first tick ≫ steady tick).
* ``cold``  — ``prewarm=True, strict_prewarm=True``, fresh cache dir:
  full AOT compile at init, zero mid-serve compiles (strict mode raises
  otherwise).
* ``warm``  — same flags, the ``cold`` run's cache dir: the same
  executables come off disk.

Exits non-zero on token divergence, a mid-serve compile in a prewarmed
run, or warm bring-up not beating cold; the CI ``bench-trajectory`` job
runs ``--smoke`` and uploads ``BENCH_9.json``.

Standalone:  PYTHONPATH=src python benchmarks/serve_coldstart.py [--smoke]
Also exposes ``run(quick)`` rows for the benchmarks.run CSV harness.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
from typing import Dict, List

try:
    from benchmarks.common import Row, write_bench
except ModuleNotFoundError:            # invoked as a script from anywhere
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks.common import Row, write_bench

ARCHS = ("llama3.2-1b", "gemma2-9b")

# one bring-up + trace, run in a child process; prints one JSON line
_CHILD = r"""
import dataclasses, json, sys
import jax, numpy as np
from repro.configs import get_config
from repro.models import init_model
from repro.serve import ServeEngine, enable_compile_cache, synthetic_request

spec = json.loads(sys.argv[1])
enable_compile_cache()
cfg = get_config(spec["arch"], smoke=True)
cfg = cfg.replace(sparsity=dataclasses.replace(
    cfg.sparsity, mode="compressed", impl="xla"))
params, _ = init_model(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
reqs = [synthetic_request(cfg, rng, rid=i, prompt_len=p, max_new_tokens=g)
        for i, (p, g) in enumerate(zip(spec["plens"], spec["gens"]))]
eng = ServeEngine(params, cfg, n_slots=spec["slots"],
                  max_len=max(spec["plens"]) + max(spec["gens"]),
                  kv="paged", block_size=4, prewarm=spec["prewarm"],
                  strict_prewarm=spec["prewarm"])
res = eng.run(reqs)
st = eng.stats()
print(json.dumps({
    "tokens": {str(r.rid): res[r.rid].tokens.tolist() for r in reqs},
    "init_s": st["init_seconds"],
    "prewarm_s": st["prewarm_seconds"],
    "compile_s": st["compile_seconds"],
    "mid_serve_compiles": int(st["mid_serve_compiles"]),
    "prewarmed": int(st["prewarmed_executables"]),
    "expected": int(st["executables_expected"]),
    "first_tick_s": st["first_tick_s"],
    "steady_tick_s": st["steady_tick_s"],
    "events": eng.compile_events(),
}))
"""


def _bring_up(arch: str, cache_dir: str, prewarm: bool, plens, gens,
              slots: int) -> Dict:
    spec = dict(arch=arch, cache_dir=cache_dir, prewarm=prewarm,
                plens=list(plens), gens=list(gens), slots=slots)
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, env.get("PYTHONPATH")) if p)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run([sys.executable, "-c", _CHILD, json.dumps(spec)],
                          capture_output=True, text=True, env=env,
                          timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"bring-up child failed:\n{proc.stderr[-4000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench_arch(arch: str, plens=(3, 7, 10, 5, 8), gens=(6, 4, 5, 6, 3),
               slots: int = 3) -> Dict:
    tmp = tempfile.mkdtemp(prefix="coldstart-")
    try:
        # lazy baseline and the cold prewarmed run get their own fresh
        # cache dirs; warm reuses cold's so compile() is a disk hit
        lazy = _bring_up(arch, os.path.join(tmp, "lazy"), False,
                         plens, gens, slots)
        cold = _bring_up(arch, os.path.join(tmp, "aot"), True,
                         plens, gens, slots)
        warm = _bring_up(arch, os.path.join(tmp, "aot"), True,
                         plens, gens, slots)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    out: Dict = {"arch": arch, "plens": list(plens), "gens": list(gens),
                 "slots": slots}
    for name, r in (("lazy", lazy), ("cold", cold), ("warm", warm)):
        out[name] = {
            "bringup_s": round(r["init_s"], 4),
            "prewarm_s": round(r["prewarm_s"], 4),
            "compile_s": round(r["compile_s"], 4),
            "mid_serve_compiles": r["mid_serve_compiles"],
            "prewarmed": r["prewarmed"],
            "expected": r["expected"],
            "first_tick_ms": round(r["first_tick_s"] * 1e3, 3),
            "steady_tick_ms": round(r["steady_tick_s"] * 1e3, 3),
            "executables": [
                {"entry": e["entry"], "label": e["label"],
                 "phase": e["phase"], "seconds": round(e["seconds"], 4),
                 "trace_seconds": round(e["trace_seconds"], 4)}
                for e in r["events"]],
        }
    # the tentpole claims, as checkable facts:
    # 1. prewarming changes when compilation happens, never what is
    #    computed: all three engines emit identical tokens
    out["token_match"] = lazy["tokens"] == cold["tokens"] == warm["tokens"]
    # 2. the prewarmed executable set covers the whole trace (strict mode
    #    in the child already raises on any miss) and is exactly the
    #    enumerated set
    out["prewarm_ok"] = (
        cold["mid_serve_compiles"] == 0 and warm["mid_serve_compiles"] == 0
        and cold["prewarmed"] == cold["expected"] > 0
        and lazy["mid_serve_compiles"] > 0)   # the bill prewarm removes
    # 3. the persistent cache makes the second bring-up strictly cheaper
    out["warm_ok"] = warm["init_s"] < cold["init_s"]
    out["ok"] = bool(out["token_match"] and out["prewarm_ok"]
                     and out["warm_ok"])
    return out


def bench(archs: List[str], **kw) -> Dict:
    report = {"bench": "serve_coldstart", "archs": {}, "ok": True}
    for arch in archs:
        res = bench_arch(arch, **kw)
        report["archs"][arch] = res
        report["ok"] &= res["ok"]
    return report


def run(quick: bool = True) -> List[Row]:
    rows: List[Row] = []
    rep = bench(["llama3.2-1b"] if quick else list(ARCHS))
    for arch, r in rep["archs"].items():
        rows.append((
            f"serve_coldstart_{arch.split('-')[0]}",
            r["cold"]["bringup_s"] * 1e6,
            f"warm{r['warm']['bringup_s']:.2f}s"
            f"vs{r['cold']['bringup_s']:.2f}s|"
            f"midserve{r['cold']['mid_serve_compiles']}|"
            f"first{r['lazy']['first_tick_ms']:.0f}"
            f"vs{r['cold']['first_tick_ms']:.0f}ms|"
            f"match{int(r['token_match'])}"))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default=",".join(ARCHS),
                    help="comma list from {%s}" % ",".join(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI iteration (llama only)")
    ap.add_argument("--out", default="BENCH_9.json")
    args = ap.parse_args()

    archs = (["llama3.2-1b"] if args.smoke
             else [a.strip() for a in args.archs.split(",") if a.strip()])
    for a in archs:
        if a not in ARCHS:
            raise SystemExit(f"unknown arch {a!r}; known: {list(ARCHS)}")
    report = bench(archs)

    for arch, r in report["archs"].items():
        la, co, wa = r["lazy"], r["cold"], r["warm"]
        print(f"{arch}: bring-up lazy {la['bringup_s']:.2f}s / cold "
              f"{co['bringup_s']:.2f}s / warm {wa['bringup_s']:.2f}s | "
              f"{co['prewarmed']}/{co['expected']} executables prewarmed, "
              f"mid-serve compiles {la['mid_serve_compiles']} lazy vs "
              f"{co['mid_serve_compiles']} prewarmed | first tick "
              f"{la['first_tick_ms']:.0f}ms lazy vs "
              f"{co['first_tick_ms']:.0f}ms prewarmed (steady "
              f"{co['steady_tick_ms']:.0f}ms) | tokens "
              f"{'MATCH' if r['token_match'] else 'MISMATCH'}")

    write_bench(report, args.out)
    if not report["ok"]:
        raise SystemExit("cold-start bench failed an invariant (token "
                         "mismatch, mid-serve compile in a prewarmed run, "
                         "or warm bring-up not beating cold)")


if __name__ == "__main__":
    main()
