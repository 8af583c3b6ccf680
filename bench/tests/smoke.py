"""A cell at a size the CPU runs in seconds, for the tests: the program's
llama-architecture path (``program_arch`` deepseek-67b) at tiny widths,
with the chat mix scaled down."""

from bench import spec

MODEL = {
    "name": "smoke", "program_arch": "deepseek-67b", "family": "llama",
    "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "num_hidden_layers": 2, "vocab_size": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16",
    "sparsity": {"n": 2, "m": 4},
    "engine": {"n_slots": 4, "max_len": 96, "block_size": 8, "n_blocks": 64,
               "prefill_buckets": [4, 8, 16, 32]},
}

MIX = {
    "kind": "poisson", "base_seed": 5, "warmup_s": 0.5, "after_s": 5.0,
    "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 60,
               "palette": 16},
    "output": {"median": 6, "sigma": 0.5, "min": 2, "max": 30},
}

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def cell(rate: float = 8.0, limits=None, mix=None) -> spec.Cell:
    return spec.Cell(name="smoke", chips=1, config=MODEL,
                     mix_name="smoke", mix=mix or MIX,
                     cell={"rate_per_s": rate,
                           "limits": limits or {"logit_gap_max": 0.05}},
                     end_to_end=[{"name": n, "unit": u} for n, u in (
                         ("ttft_p90_ms", "ms"), ("itl_p50_ms", "ms"),
                         ("itl_p99_ms", "ms"),
                         ("output_tokens_per_s", "tokens/s"),
                         ("setup_s", "s"))],
                     per_layer=[])
