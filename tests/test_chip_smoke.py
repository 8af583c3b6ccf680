"""chip_smoke.py's serve-and-compare, rehearsed on the CPU at smoke size.

On a TPU the script runs llama3.2-1b at full width; here the same functions
run the reduced config, with the Pallas kernels interpreted: the kernel
engine against the XLA reference on one device, and the TP engine against
the one-device engine over forced host devices.
"""

import importlib.util
import os

import jax

from conftest import run_child

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _smoke_model():
    from repro.configs import get_config
    from repro.models import init_model
    cfg = get_config("llama3.2-1b", smoke=True)
    return cfg, init_model(jax.random.PRNGKey(0), cfg)[0]


def test_one_chip_path_matches_xla_reference():
    cs = _chip_smoke()
    cfg, params = _smoke_model()
    reqs = cs.build_requests(cfg, 0, n=4, prompt_lens=(6, 20), gen=4)
    rep = cs.one_chip(cfg, params, reqs, n_slots=2, buckets=(8, 12),
                      block_size=4, native=False)
    assert rep["logits_ok"], rep
    assert rep["decode_rows_compared"] == 2, rep
    # f32 smoke model: the two paths agree to rounding, so every token does
    assert rep["tokens_agree"] == rep["tokens_total"] == 16, rep


def test_main_refuses_without_a_tpu(capsys):
    """No accelerator: a non-zero exit and no result line."""
    cs = _chip_smoke()
    assert cs.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_tensor_parallel_path_matches_one_device():
    code = r"""
import importlib.util, json, sys
import jax
from repro.configs import get_config
from repro.models import init_model
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
cfg = get_config("llama3.2-1b", smoke=True)
params, _ = init_model(jax.random.PRNGKey(0), cfg)
reqs = cs.build_requests(cfg, 0, n=4, prompt_lens=(6, 20), gen=4)
print(json.dumps(cs.tensor_parallel(cfg, params, reqs, 4, n_slots=2,
                                    buckets=(8, 12), block_size=4)))
"""
    rep = run_child(code, devices=4, argv=[os.path.join(ROOT, "chip_smoke.py")])
    assert rep["logits_ok"], rep
    assert rep["tokens_agree"] == rep["tokens_total"] == 16, rep
