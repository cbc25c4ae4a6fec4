"""A rehearsed run must come out correct with the program as it is, and
not correct with each fault the cells can have planted underneath."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def run(fault: str, workload: str, tmp_path_factory, size: str = "tiny",
        strategy: str | None = None, md2_bank: bool = False,
        md2_planned: bool = False) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(
                   tmp_path_factory.getbasetemp() / "jax_cache"))
    extra = [] if strategy is None else ["--strategy", strategy]
    if md2_bank:
        extra.append("--md2-bank")
    if md2_planned:
        extra.append("--md2-planned")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_run.py"), fault, size,
         *extra, "--workload", workload, "--seed", "3000000123",
         "--seconds", "2" if size == "tiny" else "20"],
        capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _case(*values):
    """A parametrised case named as pytest names a plain tuple, leaving out
    a strategy of ``None`` (the cell's own), an unforced bank and an
    unplanned ``md2``."""
    return pytest.param(*values, id="-".join(v for v in values if v))


MD2_BANK = "md2-bank"
MD2_PLANNED = "md2-planned"


# "cache_only", the paper's "Cache Only" baseline, run on the OOI cell: the
# engine serves every window through its static block replay (_run_static).
# "md2", the paper's mining baseline, run on the GAGE cell with its ARIMA on
# the fixed-width bank, online and through a window planner.
@pytest.mark.parametrize("workload,strategy,bank,planned", [
    _case("ooi_vdc_128g.paper", None, None, None),
    _case("gage_vdc_32g.paper", None, None, None),
    _case("ooi_vdc_128g.paper", "cache_only", None, None),
    _case("gage_vdc_32g.paper", "md2", MD2_BANK, None),
    _case("gage_vdc_32g.paper", "md2", MD2_BANK, MD2_PLANNED),
])
def test_sound_run_is_correct(workload, strategy, bank, planned,
                              tmp_path_factory):
    out = run("none", workload, tmp_path_factory, strategy=strategy,
              md2_bank=bank == MD2_BANK, md2_planned=planned == MD2_PLANNED)
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
    _case("md2_single_series", "gage_vdc_32g.paper", "tiny", "md2"),
])
def test_fault_is_caught(fault, workload, size, strategy, tmp_path_factory):
    out = run(fault, workload, tmp_path_factory, size, strategy)
    assert not out["correct"], out["checks"]
    if strategy == "md2":
        # md2's faults lie in its forecasts, so they show in its ops
        assert out["checks"]["ops_differ"]["value"] > 0


@pytest.mark.parametrize("planned", [
    pytest.param(None, id="online"),
    pytest.param(MD2_PLANNED, id="planned"),
])
def test_md2_dropped_op_is_caught(planned, tmp_path_factory):
    """One op left out of one request's ``md2`` ops, where the route in use
    produces them, is one request whose ops differ; what it does to later
    outcomes is not asked."""
    out = run("md2_op_dropped", "gage_vdc_32g.paper", tmp_path_factory,
              strategy="md2", md2_bank=True,
              md2_planned=planned == MD2_PLANNED)
    assert not out["correct"], out["checks"]
    assert out["checks"]["ops_differ"]["value"] == 1
