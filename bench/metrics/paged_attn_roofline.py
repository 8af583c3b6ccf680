"""Kernels: fused paged GQA decode attention's share of its roofline inside
decode ticks.

Required work per tick, from the live rows' context lengths alone: for each
layer and each live row of context L, its K and V (L * kv_heads * head_dim
values each, bf16), and 4 * L * heads * head_dim FLOPs (scores and context).
The least time per row and layer is the larger of FLOPs over peak and bytes
over bandwidth; the share is its sum over the device time of the kernel's
events inside the same ticks' ``bench.decode`` spans.

The kernel's ``pallas_call`` has no ``name=``: the trace prints it as
``%closed_call.<n>`` with its float32 result ``[slots, heads,
kv_heads * head_dim]``, which ``kernel()`` matches."""

from bench import trace as tr


def kernel(model: dict) -> str:
    lanes = model["num_key_value_heads"] * model["head_dim"]
    return (rf"^%closed_call\.\d+ f32\[\d+,"
            rf"{model['num_attention_heads']},{lanes}\]$")


def work(model: dict, length: int):
    """(FLOPs, bytes) one layer's attention requires for one row."""
    h, kv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                 model["head_dim"])
    return 4.0 * length * h * hd, 2 * length * kv * hd * 2


def required_s(model: dict, kv_lens, peaks: dict) -> float:
    t = 0.0
    for length in kv_lens:
        flops, nbytes = work(model, int(length))
        t += max(flops / peaks["bf16_flops_per_s"],
                 nbytes / peaks["hbm_bytes_per_s"])
    return t * model["num_hidden_layers"]


def read(run):
    if run.trace is None or not run.traced_ticks:
        return None
    need = sum(required_s(run.model, t.kv_lens, run.peaks)
               for t in run.traced_ticks)
    got = sum(e.dur for ops in run.trace.ops
              for evs in tr.within(ops, run.trace.spans, "bench.decode",
                                   kernel(run.model)).values() for e in evs)
    if not got:
        return None
    return 100.0 * need / (got / len(run.trace.ops))
