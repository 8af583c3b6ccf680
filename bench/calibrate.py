"""Chip-side calibration of a cell: the knee sweep that fixes an open-loop
cell's rate, and the readings that set the output check's limit.

    python bench/calibrate.py sweep   --workload <cell> --seed <n> --rates 2,3,4
    python bench/calibrate.py control --workload <cell> --seeds 5,6,7

``sweep`` builds a fresh engine for each rate and offers it for
``--seconds`` after ``--warmup`` seconds, then drains for at most a
minute: a rate is sustained when the queue wait stays flat across the
window and the window's requests finish.  ``control`` runs, for each seed, the
cell at its own rate for a short window, then reads the program's
``logit_gap_max`` and the int8 and fp8 controls' readings over the same
sample (``bench/check.py``).  Each prints one JSON line per rate or seed and
writes them to ``chiprun_out/calibrate-<mode>-<cell>.jsonl``.  Neither is
part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _build(cell, seed):
    import jax
    from bench import program, weights
    model = cell.config
    cfg = program.arch_config(model)
    params = jax.block_until_ready(
        program.params_fn(cfg, model)(weights.seed_key(seed)))
    return program.engine(params, cfg, model["engine"])


def _offer(eng, cell, seed, rate, warmup, seconds, drain_limit=150.0):
    from bench import program, serve_loop, traffic
    mix = dict(cell.mix, warmup_s=warmup)
    load = traffic.build(mix, seed=seed, vocab=cell.config["vocab_size"],
                         warmup_s=warmup, window_s=seconds, rate=rate)
    program.prime(eng, [a.prompt_len for a in load.arrivals])
    log = serve_loop.OpenLoop(eng, load, (warmup, warmup + seconds),
                              drain_limit=drain_limit).run()
    return load, log


def _summary(log, eng_stats) -> dict:
    import numpy as np
    from bench import latency
    recs = log.in_window()
    w0, w1 = log.window
    waits = np.array([r.admitted - r.arrival for r in recs
                      if not math.isnan(r.admitted)])
    arr = np.array([r.arrival for r in recs if not math.isnan(r.admitted)])
    slope = float(np.polyfit(arr, waits, 1)[0]) if len(arr) > 2 else None
    third = (w1 - w0) / 3
    early = waits[arr < w0 + third]
    late = waits[arr >= w1 - third]
    return dict(latency.end_to_end(log), arrivals=len(recs),
                failed=sum(1 for r in recs if r.failed),
                queue_wait_p90_ms=1e3 * latency.percentile(waits, 90),
                queue_wait_first_third_ms=1e3 * float(np.mean(early))
                if len(early) else None,
                queue_wait_last_third_ms=1e3 * float(np.mean(late))
                if len(late) else None,
                queue_wait_slope_s_per_s=slope,
                drain_s=log.end - w1, occupancy=eng_stats["occupancy"],
                preemptions=eng_stats.get("preemptions"))


def _emit(path, row):
    print(json.dumps(row), flush=True)
    with open(path, "a") as f:
        f.write(json.dumps(row) + "\n")


def sweep(cell, seed, rates, warmup, seconds, out):
    for rate in rates:
        t = time.perf_counter()
        eng = _build(cell, seed)    # a fresh pool and queue for each rate
        _, log = _offer(eng, cell, seed, rate, warmup, seconds,
                        drain_limit=60.0)
        row = dict(rate_per_s=rate, wall_s=time.perf_counter() - t,
                   **_summary(log, eng.stats()))
        del eng
        gc.collect()
        _emit(out, row)


def control(cell, seeds, warmup, seconds, out):
    import numpy as np
    from bench import check
    rate = cell.cell.get("rate_per_s")
    for seed in seeds:
        eng = _build(cell, seed)
        load, log = _offer(eng, cell, seed, rate, warmup, seconds)
        served = {rid: np.asarray(r.tokens) for rid, r in eng.results.items()
                  if not r.rejected}
        stats = eng.stats()
        del eng
        gc.collect()
        picked, prompts, toks = check.sample(log, served, load, seed, 512, 8)
        t = time.perf_counter()
        row = dict(seed=seed, rate_per_s=rate, **check.readings(
            cell.config, seed, picked, prompts, toks,
            controls=("int8", "fp8")))
        row["reference_s"] = time.perf_counter() - t
        row["served_in_window"] = _summary(log, stats)["arrivals"]
        _emit(out, row)
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="")
    ap.add_argument("--warmup", type=float, default=15.0)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import program, spec
    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    program.enable_compile_cache()
    cell = spec.load(args.workload)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    out = os.path.join(ROOT, "chiprun_out",
                       f"calibrate-{args.mode}-{args.workload}.jsonl")
    if args.mode == "sweep":
        sweep(cell, args.seed, [float(r) for r in args.rates.split(",")],
              args.warmup, args.seconds, out)
    else:
        control(cell, [int(s) for s in args.seeds.split(",")], args.warmup,
                args.seconds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
