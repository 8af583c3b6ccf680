"""Kernels: fused paged GQA decode attention's share of its roofline inside
decode ticks.

Required work per tick, from the live rows' context lengths alone: for each
layer and each live row, what the model's family says one layer's attention
requires for that context (``paged_attn_work``: FLOPs, bytes).  The least
time per row and layer is the larger of FLOPs over peak and bytes over
bandwidth; the share is its sum over the device time of the kernel's events
(``paged_attn_kernel``, the name the trace prints for it) inside the same
ticks' ``bench.decode`` spans.  A family that does not run the kernel
gets no reading."""

from bench import families
from bench import trace as tr


def required_s(model: dict, kv_lens, peaks: dict) -> float:
    work = families.family(model).paged_attn_work
    t = 0.0
    for length in kv_lens:
        flops, nbytes = work(model, int(length))
        t += max(flops / peaks["bf16_flops_per_s"],
                 nbytes / peaks["hbm_bytes_per_s"])
    return t * model["num_hidden_layers"]


def read(run):
    if run.trace is None or not run.traced_ticks:
        return None
    fam = families.family(run.model)
    if not hasattr(fam, "paged_attn_work"):
        return None
    need = sum(required_s(run.model, t.kv_lens, run.peaks)
               for t in run.traced_ticks)
    got = sum(e.dur for ops in run.trace.ops
              for evs in tr.within(ops, run.trace.spans, "bench.decode",
                                   fam.paged_attn_kernel(run.model)).values()
              for e in evs)
    if not got:
        return None
    return 100.0 * need / (got / len(run.trace.ops))
