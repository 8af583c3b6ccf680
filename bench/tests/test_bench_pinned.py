"""Readings pinned to what the harness gave while the llama decoder was
written into it, before model families moved to ``bench/families``: the
smoke model's weight bits, its reference logits (float32, and the int8 and
fp8 controls) on a fixed sequence, and the required work of one decode
tick for both configuration files.  What moves one of these moves what a
cell reads."""

import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

from bench import families, reference, weights
from bench.spec import HERE
from bench.tests import smoke

SEED = 2 ** 33 + 5
KV_LENS = [1, 17, 256, 1000, 1279]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

# sha256 of the raw bytes, first 16 hex digits
WEIGHTS = {"emb": "6974edb87bf6c332", "wd": "069431e36a4c5d39",
           "wg": "36260caf89eccc4f", "wk": "6120f97328d868a1",
           "wo": "b4bdcf89ed349e95", "wq": "59db454a99445176",
           "wu": "21e2c86d11c7fdf2", "wv": "0af330bd60aae7ed"}
LOGITS = {"f32": "fe15c402459ea57b", "int8": "307e25ff5c73563c",
          "fp8": "aeec8f86ea9980c5"}
WORK = {
    "deepseek-67b-6L": {
        "nm_required_s": [0.0057058705347985345, 0.005714182564102564,
                          0.005836784996336997],
        "flops": 29652353024.0,
        "attn_work": [[32768.0, 4096], [557056.0, 69632],
                      [8388608.0, 1048576], [32768000.0, 4096000],
                      [41910272.0, 5238784]],
        "attn_required_s": 7.66087032967033e-05,
        "kernel": r"^%closed_call\.\d+ f32\[\d+,64,1024\]$"},
    "mistral-large-123b-4L": {
        "nm_required_s": [0.007606997177045176, 0.007614759072039071,
                          0.007729247023199024],
        "flops": 32210878464.0,
        "attn_work": [[49152.0, 4096], [835584.0, 69632],
                      [12582912.0, 1048576], [49152000.0, 4096000],
                      [62865408.0, 5238784]],
        "attn_required_s": 5.1072468864468865e-05,
        "kernel": r"^%closed_call\.\d+ f32\[\d+,96,1024\]$"},
}


def _digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("role", sorted(WEIGHTS))
def test_weight_bits(role):
    key = weights.seed_key(SEED)
    if role == "emb":
        w = weights.embedding(key, smoke.MODEL)
    else:
        w = families.family(smoke.MODEL).layer(key, smoke.MODEL, 0)[role]
    assert _digest(w) == WEIGHTS[role]


@pytest.mark.parametrize("precision", sorted(LOGITS))
def test_reference_logits(precision):
    seq = ((np.arange(40) * 37 + 11) % smoke.MODEL["vocab_size"]).astype(
        np.int32)
    ref = reference.Reference(smoke.MODEL, SEED, precision)
    logits = ref.logits([seq], [np.array([0, 7, 19, 39])])[0]
    assert _digest(logits) == LOGITS[precision]


@pytest.mark.parametrize("config", sorted(WORK))
def test_tick_work(config):
    with open(os.path.join(HERE, "configs", f"{config}.json")) as f:
        model = json.load(f)
    fam, want = families.family(model), WORK[config]
    nm, pa = _reader("nm_spmv_roofline"), _reader("paged_attn_roofline")
    assert [nm.required_s(model, rows, PEAKS)
            for rows in (1, 5, 64)] == want["nm_required_s"]
    assert fam.decode_flops(model, KV_LENS) == want["flops"]
    assert [list(fam.paged_attn_work(model, n))
            for n in KV_LENS] == want["attn_work"]
    assert pa.required_s(model, KV_LENS, PEAKS) == want["attn_required_s"]
    assert fam.paged_attn_kernel(model) == want["kernel"]
