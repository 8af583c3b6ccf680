"""Run one benchmark cell once on the chip and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``: a model configuration
(``bench/configs``) under a traffic mix (``bench/traffic``).  The run makes
the weights on the device from the seed, builds the serving engine (every
executable prewarmed, from the persistent compile cache where it can),
offers the mix's warm-up traffic, then measures ``--seconds`` of traffic
and follows every request that arrived in the window to its end, still
offering load.  ``setup_s`` is everything from the process's start to the
window's open.

Then it checks what the window served (``bench/check.py``): a sample of the
finished requests goes through the plain reference, and the widest gap by
which a served token's reference logit lies below the reference's best must
stay within the cell's limit (``bench/cells/<cell>.json``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the first ``TRACE_S`` seconds of the window with ``jax.profiler`` and
reports the per-layer metrics, device busy time and a breakdown.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_S = 10.0          # traced seconds, from the window's open
CHECK_MIN_TOKENS = 512  # served tokens the output check covers, at least
CHECK_MAX_REQUESTS = 8
DRAIN_LIMIT_S = 150.0   # after the close, what has not finished has failed


@dataclasses.dataclass
class Run:
    """What the metric readers see of a finished run."""
    log: object                     # serve_loop.Log
    stats: Dict[str, float]         # engine counters
    model: dict                     # configuration file
    peaks: dict
    trace: object = None            # trace.Trace, traced runs only
    busy_s: float = 0.0
    traced_ticks: List = dataclasses.field(default_factory=list)
    traced_admissions: List = dataclasses.field(default_factory=list)


def say(label: str, obj) -> None:
    print(f"{label}: {json.dumps(obj)}", flush=True)


class CompileWatch:
    """Every executable JAX builds or loads from its persistent cache, with
    when it finished and what it took.  One per process (``get``)."""

    _one = None

    @classmethod
    def get(cls) -> "CompileWatch":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.events: List[tuple] = []
        self._name = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "",
            **_) -> None:
        if event == self._name:
            self.events.append((time.perf_counter(), duration, fun_name))

    def between(self, a: float, b: float) -> List[tuple]:
        return [(d, n) for t, d, n in self.events if a <= t < b]


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def _traced(items, on: float, off: float):
    return [x for x in items if on <= x.start and x.end <= off]


def _reduce_trace(run: Run, trace, marks: Dict[str, float], log) -> None:
    """Attach the trace to ``run``: device busy time, and the ticks and
    admissions the trace holds, each decode span paired with its tick."""
    from bench import serve_loop
    from bench import trace as tr
    on, off = marks["trace_on"] - log.t0, marks["trace_off"] - log.t0
    run.trace = trace
    run.busy_s = (sum(tr.busy_s(ops, trace.window) for ops in trace.ops)
                  / max(len(trace.ops), 1))
    run.traced_admissions = _traced(log.admissions, on, off)
    ticks = _traced(log.ticks, on, off)
    spans, run.traced_ticks = tr.pair(
        [s for s in trace.spans if s.name == serve_loop.DECODE], ticks)
    if len(spans) != len(ticks):
        say("warning", f"{len(spans)} of {len(ticks)} traced ticks paired "
                       f"with their decode spans")
    trace.spans = sorted([s for s in trace.spans
                          if s.name != serve_loop.DECODE] + spans,
                         key=lambda e: e.start)


def execute(cell, seed: int, seconds: float, traced: bool, devices,
            peaks: dict, *, check_model: bool = True,
            fault: Optional[Callable] = None) -> dict:
    """One run of ``cell`` (``bench.spec.Cell``).  ``fault``, for tests,
    is applied to the engine before any traffic."""
    import jax
    import numpy as np
    from bench import check, latency, metrics, program, serve_loop
    from bench import trace as tr
    from bench import traffic, weights

    model, geometry = cell.config, cell.config["engine"]
    parts = {"cache_dir": program.enable_compile_cache()}
    watch = CompileWatch.get()
    t = time.perf_counter()
    cfg = program.arch_config(model, check=check_model)
    params = jax.block_until_ready(
        program.params_fn(cfg, model)(weights.seed_key(seed)))
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    eng = program.engine(params, cfg, geometry)
    del params
    parts["engine_s"] = time.perf_counter() - t
    parts["engine_compile_s"] = eng.stats()["compile_seconds"]
    if fault is not None:
        fault(eng)
    mix = cell.mix
    load = traffic.build(mix, seed=seed, vocab=model["vocab_size"],
                         warmup_s=float(mix["warmup_s"]), window_s=seconds,
                         rate=cell.cell.get("rate_per_s"))
    w0, w1 = float(mix["warmup_s"]), float(mix["warmup_s"]) + seconds
    t = time.perf_counter()
    parts["primed_lengths"] = program.prime(
        eng, [a.prompt_len for a in load.arrivals])
    parts["prime_s"] = time.perf_counter() - t
    marks: Dict[str, float] = {}
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    hooks = {w0: lambda: marks.setdefault("open", time.perf_counter())}
    if traced:
        t_on, t_off = w0, w0 + min(TRACE_S, seconds)

        def start():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            marks["trace_on"] = time.perf_counter()

        def stop():
            marks["trace_off"] = time.perf_counter()
            jax.profiler.stop_trace()

        hooks[t_on + 1e-6] = start
        hooks[t_off] = stop
    loop_start = time.perf_counter()
    log = serve_loop.OpenLoop(eng, load, (w0, w1), drain_limit=DRAIN_LIMIT_S,
                              at=hooks).run()
    setup_s = marks["open"] - T_START
    parts["warmup_traffic_s"] = marks["open"] - loop_start
    parts["compiles_before_window"] = len(watch.between(0, marks["open"]))
    in_win = watch.between(marks["open"], log.t0 + w1)
    parts["compiles_in_window"] = len(in_win)
    parts["compile_s_in_window"] = sum(d for d, _ in in_win)
    parts["compiled_in_window"] = sorted({n for _, n in in_win})
    stats = eng.stats()
    device = device_info(devices)
    recs = log.in_window()
    failed = [r for r in recs if r.failed or not r.done]
    say("setup parts (s)", parts)
    say("run", {"attempted": len(recs), "failed": len(failed),
                "ticks": len(log.ticks), "admissions": len(log.admissions),
                "drain_s": log.end - w1, "occupancy": stats["occupancy"],
                "preemptions": stats.get("preemptions"),
                "mid_serve_compiles": stats["mid_serve_compiles"]})
    served = {rid: np.asarray(res.tokens) for rid, res in eng.results.items()
              if not res.rejected}
    del eng
    gc.collect()

    result = {"correct": False, "attempted": len(recs),
              "failed": len(failed), "metrics": {}, "device": device,
              "setup": parts}
    run = Run(log=log, stats=stats, model=model, peaks=peaks)
    if traced:
        try:
            trace = tr.load(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        _reduce_trace(run, trace, marks, log)
        device.update(busy_s=run.busy_s, window_s=trace.window_s)
        ops0 = trace.ops[0] if trace.ops else []
        result["breakdown"] = {
            "device_ops": tr.top_ops(ops0, trace.window),
            "idle_gaps": tr.gaps_by_host(ops0, trace.spans, trace.window)}
        for m in cell.per_layer:
            v = metrics.reader(m["name"])(run)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v,
                                                "unit": m["unit"]}
    else:
        values = dict(latency.end_to_end(log), setup_s=setup_s)
        for m in cell.end_to_end:
            v = values[m["name"]]
            result["metrics"][m["name"]] = {
                "value": v if math.isfinite(v) else None, "unit": m["unit"]}

    picked, prompts, tokens = check.sample(
        log, served, load, seed, CHECK_MIN_TOKENS, CHECK_MAX_REQUESTS)
    values = {"requests_unfinished": float(len(failed))}
    if picked:
        t = time.perf_counter()
        values.update(check.readings(model, seed, picked, prompts, tokens))
        say("output check", dict(values, reference_s=time.perf_counter() - t))
    limits = dict(cell.cell.get("limits", {}), requests_unfinished=0.0)
    ok, table = check.verdict(values, limits)
    result["correct"] = ok
    result["checks"] = table
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import check, spec
    from bench.peaks import peaks_for
    cell = spec.load(args.workload)
    try:
        import jax
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"bench: cannot import the program: {e}", file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX sees {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1
    peaks = peaks_for(devices[0].device_kind)
    result = execute(cell, args.seed, args.seconds, bool(args.trace),
                     devices[:cell.chips], peaks)
    for line in check.describe(result["checks"]):
        print(line, file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
