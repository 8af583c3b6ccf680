"""Device, whole decode step: FLOPs the window's decode ticks require, over
their host-clock duration times the chip's bf16 peak.

Per live row and tick: 2 FLOPs per kept (non-zero) linear weight, 2 per LM
head weight (vocab x hidden), and 4 * context * heads * head_dim per layer
for attention.  Idle slots and padding count nothing."""

from bench import weights


def flops(model: dict, kv_lens) -> float:
    n, m = model["sparsity"]["n"], model["sparsity"]["m"]
    kept = sum(o * k for o, k in weights.shapes(model).values()) * n // m
    layers = model["num_hidden_layers"]
    per_row = 2.0 * (kept * layers
                     + model["vocab_size"] * model["hidden_size"])
    attn = 4.0 * model["num_attention_heads"] * model["head_dim"] * layers
    return per_row * len(kv_lens) + attn * float(sum(int(x) for x in kv_lens))


def read(run):
    w0, w1 = run.log.window
    ticks = [t for t in run.log.ticks if w0 <= t.start and t.end <= w1]
    dur = sum(t.end - t.start for t in ticks)
    if not dur:
        return None
    need = sum(flops(run.model, t.kv_lens) for t in ticks)
    return 100.0 * need / (dur * run.peaks["bf16_flops_per_s"])
