"""The entry point refuses to measure without a TPU, the peaks table
refuses an unknown chip, and every cell of ``BENCHMARK.json`` resolves to
files that exist, by name alone."""

import json
import os
import subprocess
import sys

import pytest

from bench import metrics, spec
from bench.peaks import peaks_for


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(spec.HERE, "run.py"), "--workload",
         "deepseek67b-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"metrics"' not in p.stdout


def test_peaks_refuse_an_unknown_device_kind():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")


def _benchmark():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload", [w["name"] for w in _benchmark()[
    "workloads"]])
def test_every_cell_resolves_by_name(workload):
    cell = spec.load(workload)
    assert cell.config["name"] == next(
        w["config"] for w in _benchmark()["workloads"]
        if w["name"] == workload)
    assert "engine" in cell.config and "kind" in cell.mix
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    for m in cell.per_layer:
        assert callable(metrics.reader(m["name"]))
    if cell.mix["kind"] != "backlog":
        assert cell.cell["rate_per_s"] > 0
