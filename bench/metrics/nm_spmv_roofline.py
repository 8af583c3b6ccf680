"""Kernels: the compressed decode linears' (``nm_spmv``) share of their
roofline inside decode ticks.

Required work per tick is counted from the model's shapes and the tick's
live rows alone, whatever implements it: for each n:m linear one decode
tick runs (as the model's family lists them, ``nm_spmv_linears``), the
bf16 values (n/m of the dense weight), the in-block indices at
ceil(log2 m) bits per kept value, the live rows' input and output in bf16,
and 2 * kept weights * rows FLOPs.  The least time is the larger of FLOPs
over peak FLOP/s and bytes over peak bytes/s, summed over linears and
ticks; the share is that over the device time of the kernel's events
(``KERNEL``, the name the trace prints for the decode linears' custom
calls) inside the same ticks' ``bench.decode`` spans.  A family that lists
no such linears gets no reading."""

import math

from bench import families
from bench import trace as tr

KERNEL = r"^%nm_spmv\.\d+ "


def work(o: int, k: int, n: int, m: int, rows: int):
    """(FLOPs, bytes) one ``[o, k]`` n:m linear requires for ``rows`` rows."""
    kept = o * k * n // m
    nbytes = kept * 2 + kept * math.ceil(math.log2(m)) / 8 \
        + rows * (k + o) * 2
    return 2.0 * kept * rows, nbytes


def required_s(model: dict, rows: int, peaks: dict) -> float:
    n, m = model["sparsity"]["n"], model["sparsity"]["m"]
    total = 0.0
    for layers, shapes in families.family(model).nm_spmv_linears(model):
        t = 0.0
        for o, k in shapes:
            flops, nbytes = work(o, k, n, m, rows)
            t += max(flops / peaks["bf16_flops_per_s"],
                     nbytes / peaks["hbm_bytes_per_s"])
        total += t * layers
    return total


def read(run):
    if run.trace is None or not run.traced_ticks:
        return None
    if not hasattr(families.family(run.model), "nm_spmv_linears"):
        return None
    need = sum(required_s(run.model, len(t.kv_lens), run.peaks)
               for t in run.traced_ticks)
    got = sum(e.dur for ops in run.trace.ops
              for evs in tr.within(ops, run.trace.spans, "bench.decode",
                                   KERNEL).values() for e in evs)
    if not got:
        return None
    return 100.0 * need / (got / len(run.trace.ops))
