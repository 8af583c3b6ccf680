"""Required work, counted from the model's shapes by the readers and the
model's family, against hand counts."""

import json
import os

import pytest

from bench import families
from bench.spec import HERE

CONFIGS = os.path.join(HERE, "configs")


def _load(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _model(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def test_nm_spmv_work_deepseek_w_down():
    nm = _load("nm_spmv_roofline")
    # w_down of deepseek-67b: [8192, 22016], 2:4, 64 live rows
    flops, nbytes = nm.work(8192, 22016, 2, 4, 64)
    kept = 8192 * 22016 // 2                    # 90,177,536 values
    assert kept == 90_177_536
    assert flops == 2 * kept * 64
    # bf16 values + 2-bit indices + x [64, 22016] + y [64, 8192] in bf16
    assert nbytes == kept * 2 + kept // 4 + 64 * (22016 + 8192) * 2
    assert nbytes == 206_766_080


def test_nm_spmv_work_mistral_wq_and_whole_tick():
    nm = _load("nm_spmv_roofline")
    flops, nbytes = nm.work(12288, 12288, 2, 4, 1)
    assert flops == 2 * 75_497_472
    assert nbytes == 75_497_472 * 2.25 + (12288 + 12288) * 2
    model = _model("mistral-large-123b-4L")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # memory bound at one row: a tick streams 4 layers of compressed weights
    per_layer = sum(o * k for o, k in (
        (12288, 12288), (1024, 12288), (1024, 12288), (12288, 12288),
        (28672, 12288), (28672, 12288), (12288, 28672))) // 2 * 2.25
    t = nm.required_s(model, 1, peaks)
    assert t == pytest.approx(4 * per_layer / 819e9, rel=1e-3)


def test_paged_attention_work():
    pa = _load("paged_attn_roofline")
    model = _model("deepseek-67b-6L")
    flops, nbytes = families.family(model).paged_attn_work(model, 1000)
    assert flops == 4 * 1000 * 64 * 128
    assert nbytes == 2 * 1000 * 8 * 128 * 2
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert pa.required_s(model, [1000, 1000], peaks) == pytest.approx(
        6 * 2 * max(flops / 197e12, nbytes / 819e9))


def test_decode_flops_count_live_rows_only():
    model = _model("deepseek-67b-6L")
    flops = families.family(model).decode_flops
    kept = (8192 * 8192 * 2 + 1024 * 8192 * 2 + 22016 * 8192 * 3) // 2
    per_row = 2 * (6 * kept + 102400 * 8192)
    assert flops(model, [10]) == per_row + 4 * 64 * 128 * 6 * 10
    assert flops(model, []) == 0


def test_kernels_match_the_names_the_trace_prints():
    import re
    nm = _load("nm_spmv_roofline")
    # as a v5e trace printed them (names cut by bench.trace.short)
    assert re.search(nm.KERNEL, "%nm_spmv.69 bf16[64,8192]")
    assert not re.search(nm.KERNEL, "%nm_xwt.48 bf16[128,8192]")
    ds, ml = _model("deepseek-67b-6L"), _model("mistral-large-123b-4L")
    kernel = families.family(ds).paged_attn_kernel
    assert re.search(kernel(ds), "%closed_call.13 f32[64,64,1024]")
    assert re.search(kernel(ml), "%closed_call.7 f32[64,96,1024]")
    assert not re.search(kernel(ml), "%closed_call.13 f32[64,64,1024]")
