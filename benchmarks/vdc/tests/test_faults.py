"""A rehearsed run must come out correct with the program as it is, and
not correct with each fault the cells can have planted underneath."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run(fault: str, workload: str, tmp_path_factory,
        size: str = "tiny") -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.getbasetemp() / "jax_cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), fault, size,
         "--workload", workload, "--seed", "3000000123",
         "--seconds", "2" if size == "tiny" else "20"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["ooi_vdc_128g.paper",
                                      "gage_vdc_32g.paper"])
def test_sound_run_is_correct(workload, tmp_path_factory):
    out = run("none", workload, tmp_path_factory)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"] == {}          # a rehearsal prints no device metric


@pytest.mark.parametrize("fault,workload,size", [
    ("state_unchanged", "ooi_vdc_128g.paper", "tiny"),
    ("state_unchanged", "gage_vdc_32g.paper", "tiny"),
    ("answer_altered", "ooi_vdc_128g.paper", "tiny"),
    ("answer_altered", "gage_vdc_32g.paper", "tiny"),
    # the tiny population makes too few forecasts for the bank to matter
    ("half_batch", "ooi_vdc_128g.paper", "cell"),
    ("half_batch", "gage_vdc_32g.paper", "cell"),
])
def test_fault_is_caught(fault, workload, size, tmp_path_factory):
    out = run(fault, workload, tmp_path_factory, size)
    assert not out["correct"], out["checks"]
