"""Chip smoke test: serve llama3.2-1b at full width through ``ServeEngine``.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py           # one chip: the Pallas path vs plain XLA
    python chip_smoke.py --tp 4    # four chips: TP=4 engine vs one-chip engine

One chip.  The model is llama3.2-1b at its published widths (16 layers,
d 2048, GQA 32/8, d_ff 8192, vocab 128256, bf16) with random weights drawn
from ``--seed``.  It serves 16 requests (prompts of 128-512 tokens, 32 new
tokens each) over 8 slots through the main serving path: continuous
scheduler, compressed 2:4 weights, paged KV, fused paged attention, with the
executable set prewarmed over explicit prefill buckets.  The same requests
then go through the plain XLA path (dense masked weights, ``impl="xla"``,
gather attention, "highest" matmul precision).  The prefill and first-decode
logits of the two must agree within ``LOGIT_TOL`` of the reference's logit
scale, and every executable of the Pallas engine must hold a Mosaic kernel
(``tpu_custom_call``).

``--tp 4`` runs only the TP path: the same serve at TP=4 over a ("model",)
mesh of the first four chips (compressed sparse ring for decode), against
the one-chip Pallas engine on the same requests.

The script needs a TPU: with none visible it fails and prints no result.
Lines before the last are smoke figures (host-clock times, compile seconds,
device memory), not benchmarks.  The last line of standard output is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

ARCH = "llama3.2-1b"
N_REQUESTS = 16
SLOTS = 8
PROMPT_LENS = (128, 512)         # inclusive range of prompt lengths
GEN = 32                         # new tokens per request
BUCKETS = (128, 256, 384)        # prefill buckets (max_len joins them)
BLOCK_SIZE = 8
# prefill and first-decode logits: max |test - ref| over the reference's
# max |logit|.  Both paths round activations to bf16 at the same points and
# accumulate in f32; what differs is accumulation order, which can flip a
# bf16 rounding (one ulp, 2^-8 relative) that then travels through 16 layers.
LOGIT_TOL = 2e-2


def build_requests(cfg, seed: int, n: int = N_REQUESTS,
                   prompt_lens=PROMPT_LENS, gen: int = GEN):
    """``n`` token requests with prompt lengths drawn uniformly from
    ``prompt_lens`` (inclusive) and ``gen`` new tokens each."""
    from repro.serve import Request
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n)
    return [Request(rid=i, inputs={"tokens": rng.integers(
                        0, cfg.vocab, (int(p),)).astype(np.int32)},
                    max_new_tokens=gen)
            for i, p in enumerate(lens)]


def build_engine(params, cfg, *, kernels: bool, n_slots: int, max_len: int,
                 buckets, block_size: int, mesh=None):
    """The serving engine under test (``kernels=True``: compressed weights,
    fused paged attention — the Pallas kernels on a TPU) or the plain XLA
    reference (dense masked weights, ``impl="xla"``, gather attention).
    Both prewarm their closed executable set and refuse any compile inside
    the serving loop."""
    from repro.serve import ServeEngine
    kw = dict(n_slots=n_slots, max_len=max_len, kv="paged",
              block_size=block_size, prefill_buckets=buckets, mesh=mesh,
              prewarm=True, strict_prewarm=True, compile_cache=True)
    if kernels:
        return ServeEngine(params, cfg, compressed=True, attn="fused", **kw)
    xla = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity, impl="xla"))
    return ServeEngine(params, xla, compressed=False, attn="gather", **kw)


class Tap:
    """Records an engine's logits as it serves: every prefill's, and the
    first decode tick's together with the tokens fed to it."""

    def __init__(self, eng):
        self.prefill = []
        self.decode = None
        prefill, decode = eng._prefill, eng._decode

        def tap_prefill(*args):
            out = prefill(*args)
            self.prefill.append(np.array(out[0], np.float32))
            return out

        def tap_decode(params, caches, tok, *rest):
            out = decode(params, caches, tok, *rest)
            if self.decode is None:
                # copies: on a CPU, ``tok`` may alias the engine's host
                # token buffer, which later ticks overwrite
                self.decode = (np.array(tok), np.array(out[0], np.float32))
            return out

        eng._prefill, eng._decode = tap_prefill, tap_decode


def serve(eng, reqs):
    """Run the requests to completion; the host clock covers a synchronous
    run (every tick reads its tokens back to the host)."""
    tap = Tap(eng)
    t0 = time.perf_counter()
    results = eng.run(reqs)
    wall = time.perf_counter() - t0
    tokens = sum(len(r.tokens) for r in results.values())
    return results, tap, dict(run_seconds=wall, tokens=tokens,
                              tokens_per_s=tokens / wall)


def _rel_err(test, ref) -> float:
    return float(np.abs(test - ref).max() / max(np.abs(ref).max(), 1e-30))


def compare(test_res, test_tap, ref_res, ref_tap) -> dict:
    """Logits and tokens of one engine against another over the same
    requests.  Admission depends only on prompt lengths, so both engines
    prefill the requests in the same order and their first decode ticks line
    up slot for slot; rows whose fed token differs (a prefill argmax that
    fell the other way) are left out of the decode comparison."""
    if len(test_tap.prefill) != len(ref_tap.prefill):
        raise AssertionError(f"prefill counts differ: {len(test_tap.prefill)}"
                             f" vs {len(ref_tap.prefill)}")
    prefill_err = max(_rel_err(a, b)
                      for a, b in zip(test_tap.prefill, ref_tap.prefill))
    (tok_t, dec_t), (tok_r, dec_r) = test_tap.decode, ref_tap.decode
    same = tok_t == tok_r
    decode_err = _rel_err(dec_t[same], dec_r[same]) if same.any() else 0.0
    agree = total = prefix = 0
    for rid, r in ref_res.items():
        t = test_res[rid].tokens
        eq = t == r.tokens
        agree += int(eq.sum())
        total += len(r.tokens)
        prefix += int(np.argmin(eq)) if not eq.all() else len(eq)
    return dict(prefill_logit_err=prefill_err, decode_logit_err=decode_err,
                decode_rows_compared=int(same.sum()),
                logit_scale=float(np.abs(ref_tap.prefill[0]).max()),
                tokens_agree=agree, tokens_total=total,
                tokens_agree_in_prefix=prefix,
                logits_ok=max(prefill_err, decode_err) <= LOGIT_TOL)


def kernel_executables(eng):
    """(executables, executables holding a Mosaic kernel) of an engine."""
    texts = [comp.as_text() for entry in eng._jits.values()
             for comp in entry._aot.values()]
    return len(texts), sum("tpu_custom_call" in t for t in texts)


def compile_figures(eng) -> dict:
    st = eng.stats()
    return dict(executables=int(st["prewarmed_executables"]),
                compile_seconds=st["compile_seconds"],
                init_seconds=st["init_seconds"],
                mid_serve_compiles=int(st["mid_serve_compiles"]))


def device_memory(devices) -> list:
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append(dict(id=d.id, bytes_in_use=ms.get("bytes_in_use"),
                        peak_bytes_in_use=ms.get("peak_bytes_in_use")))
    return out


def _say(label, obj):
    print(f"{label}: {json.dumps(obj)}", flush=True)


def one_chip(cfg, params, reqs, *, n_slots=SLOTS, buckets=BUCKETS,
             block_size=BLOCK_SIZE, native=True) -> dict:
    """The Pallas engine against the XLA reference on one device.  With
    ``native``, every executable of the Pallas engine must hold a Mosaic
    kernel (off a TPU the kernels run interpreted and lower to plain HLO)."""
    import jax
    max_len = max(r.prompt_len + r.max_new_tokens for r in reqs)
    geom = dict(n_slots=n_slots, max_len=max_len, buckets=buckets,
                block_size=block_size)
    eng = build_engine(params, cfg, kernels=True, **geom)
    n_exec, n_kernel = kernel_executables(eng)
    _say("smoke compile, pallas engine",
         dict(compile_figures(eng), with_kernel=n_kernel))
    if native and n_kernel != n_exec:
        raise AssertionError(f"{n_exec - n_kernel} of {n_exec} executables "
                             f"hold no tpu_custom_call")
    test_res, test_tap, figs = serve(eng, reqs)
    _say("smoke serve, pallas engine (host clock)", figs)
    _say("smoke device memory", device_memory(jax.local_devices()))
    del eng
    with jax.default_matmul_precision("highest"):
        ref = build_engine(params, cfg, kernels=False, **geom)
        _say("smoke compile, xla reference", compile_figures(ref))
        ref_res, ref_tap, figs = serve(ref, reqs)
    _say("smoke serve, xla reference (host clock)", figs)
    return compare(test_res, test_tap, ref_res, ref_tap)


def tensor_parallel(cfg, params, reqs, tp: int, *, n_slots=SLOTS,
                    buckets=BUCKETS, block_size=BLOCK_SIZE) -> dict:
    """The Pallas engine at TP=``tp`` over a ("model",) mesh against the
    one-chip Pallas engine.  Params are built whole on the first device and
    then placed by the engine; per-device bytes show the split."""
    import jax
    from repro.dist.api import make_serve_mesh
    max_len = max(r.prompt_len + r.max_new_tokens for r in reqs)
    geom = dict(n_slots=n_slots, max_len=max_len, buckets=buckets,
                block_size=block_size)
    eng = build_engine(params, cfg, kernels=True, mesh=make_serve_mesh(tp),
                       **geom)
    _say("smoke compile, tp engine", compile_figures(eng))
    _say("smoke device memory, tp engine built",
         device_memory(jax.local_devices()))
    test_res, test_tap, figs = serve(eng, reqs)
    _say("smoke serve, tp engine (host clock)", figs)
    del eng
    ref = build_engine(params, cfg, kernels=True, **geom)
    _say("smoke compile, one-chip engine", compile_figures(ref))
    ref_res, ref_tap, figs = serve(ref, reqs)
    _say("smoke serve, one-chip engine (host clock)", figs)
    return compare(test_res, test_tap, ref_res, ref_tap)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel path over this many "
                         "chips, against the one-chip engine")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    try:
        import jax
        from repro.configs import get_config
        from repro.kernels.ops import pallas_interpret
        from repro.models import init_model
        from repro.serve import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu" or pallas_interpret():
        print(f"chip_smoke: needs a TPU, JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 1
    if args.tp and len(devices) < args.tp:
        print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # one executable, not one dispatch per eager op of the init
    params = jax.block_until_ready(jax.jit(lambda k: init_model(k, cfg)[0])(
        jax.random.PRNGKey(args.seed)))
    _say("smoke init", dict(arch=ARCH, init_seconds=time.perf_counter() - t0))
    reqs = build_requests(cfg, args.seed)
    if args.tp:
        report = tensor_parallel(cfg, params, reqs, args.tp)
    else:
        report = one_chip(cfg, params, reqs)
    _say("logits and tokens against the reference", report)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", f"chip_smoke_tp{args.tp}.json"),
              "w") as f:
        json.dump(report, f, indent=1)
    if not report["logits_ok"]:
        print(f"chip_smoke: logits differ beyond {LOGIT_TOL}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
