"""A rehearsed run must come out correct with the program as it is, and
not correct with each fault the cells can have planted underneath."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run(fault: str, workload: str, tmp_path_factory, size: str = "tiny",
        strategy: str | None = None) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.getbasetemp() / "jax_cache"))
    extra = [] if strategy is None else ["--strategy", strategy]
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), fault, size,
         *extra, "--workload", workload, "--seed", "3000000123",
         "--seconds", "2" if size == "tiny" else "20"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _case(*values):
    """A parametrised case named as pytest names a plain tuple, leaving out
    a strategy of ``None`` (the cell's own)."""
    return pytest.param(*values, id="-".join(v for v in values if v))


# "cache_only", the paper's "Cache Only" baseline, run on the OOI cell: the
# engine serves every window through its static block replay (_run_static)
@pytest.mark.parametrize("workload,strategy", [
    _case("ooi_vdc_128g.paper", None),
    _case("gage_vdc_32g.paper", None),
    _case("ooi_vdc_128g.paper", "cache_only"),
])
def test_sound_run_is_correct(workload, strategy, tmp_path_factory):
    out = run("none", workload, tmp_path_factory, strategy=strategy)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"] == {}          # a rehearsal prints no device metric


@pytest.mark.parametrize("fault,workload,size,strategy", [
    _case("state_unchanged", "ooi_vdc_128g.paper", "tiny", None),
    _case("state_unchanged", "gage_vdc_32g.paper", "tiny", None),
    _case("answer_altered", "ooi_vdc_128g.paper", "tiny", None),
    _case("answer_altered", "gage_vdc_32g.paper", "tiny", None),
    # the tiny population makes too few forecasts for the bank to matter
    _case("half_batch", "ooi_vdc_128g.paper", "cell", None),
    _case("half_batch", "gage_vdc_32g.paper", "cell", None),
    _case("state_unchanged", "ooi_vdc_128g.paper", "tiny", "cache_only"),
    _case("static_answer_altered", "ooi_vdc_128g.paper", "tiny", "cache_only"),
    _case("static_counter_altered", "ooi_vdc_128g.paper", "tiny",
          "cache_only"),
])
def test_fault_is_caught(fault, workload, size, strategy, tmp_path_factory):
    out = run(fault, workload, tmp_path_factory, size, strategy)
    assert not out["correct"], out["checks"]
