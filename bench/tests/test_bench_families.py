"""The door to model families: the configuration file's ``"family"`` picks
``bench/families/<family>.py`` by name, a family module written outside
that directory carries the smoke cell through a whole run, and what no
family, or the wrong one, stands for is refused."""

import dataclasses
import os
import re
import shutil

import jax
import pytest

from bench import families, program, run, weights
from bench.tests import smoke


def test_a_family_given_from_outside_runs_the_smoke_cell(tmp_path,
                                                         monkeypatch):
    shutil.copy(os.path.join(families.DIR, "llama.py"),
                tmp_path / "copied.py")
    monkeypatch.setattr(families, "DIR", str(tmp_path))
    model = dict(smoke.MODEL, family="copied")
    assert families.known() == ["copied"]
    assert families.family(model).__file__ == str(tmp_path / "copied.py")
    cell = dataclasses.replace(smoke.cell(), config=model)
    res = run.execute(cell, seed=2 ** 34 + 3, seconds=1.0, traced=False,
                      devices=jax.devices(), peaks=smoke.PEAKS,
                      check_model=False)
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap_max"]["value"] is not None


@pytest.mark.parametrize("name", [None, "mamba"])
def test_a_missing_or_unknown_family_is_refused(name):
    model = {k: v for k, v in smoke.MODEL.items() if k != "family"}
    if name is not None:
        model["family"] = name
    assert "llama" in families.known()
    with pytest.raises(ValueError, match=re.escape(
            f"family {name!r} is not one of {families.known()}")):
        families.family(model)
    with pytest.raises(ValueError, match="is not one of"):
        program.arch_config(model, check=False)


@pytest.mark.parametrize("check, refusal", [
    (False, "deepseek-v2-lite-16b is not a llama-architecture dense model"),
    (True, "differs from the program's deepseek-v2-lite-16b")])
def test_the_llama_family_refuses_a_moe_entry(check, refusal):
    model = dict(smoke.MODEL, program_arch="deepseek-v2-lite-16b")
    with pytest.raises(ValueError, match=re.escape(refusal)):
        program.arch_config(model, check=check)


def test_role_ids_are_each_familys_own():
    seen = {weights.EMBEDDING: "embedding"}
    for name in families.known():
        for role, i in families.family({"family": name}).ROLES.items():
            assert i not in seen, (name, role, seen[i])
            seen[i] = f"{name}.{role}"
