"""The check takes a planned job's ops where the planner's windows are
routed (``delivery._route_planned_ops``), not from one planner's method:
for a tiny ``hpm`` job, the op lists captured there are the ones that
``BatchedHPMPlanner.plan_window`` returned, request for request.

The job runs in a child process (``python test_capture.py <cell>``), since
the probes replace attributes of the program for the life of a process.
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def test_hpm_capture_is_the_planners_output(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "gage_vdc_32g.paper"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    run, out = json.loads(lines[-2]), json.loads(lines[-1])
    assert run["correct"], run["checks"]
    assert out["captured"] == out["planned"] > 0
    assert out["same_ops"]


def main(workload: str) -> int:
    """One rehearsed run of ``workload``; prints, after the harness's own
    line, how many requests' ops the probes captured and the planner
    returned in the run's checked job, and whether they are the same
    lists."""
    sys.path[:0] = [os.path.dirname(HERE),
                    os.path.join(os.path.dirname(os.path.dirname(
                        os.path.dirname(HERE))), "src")]
    from repro.core import hpm
    from vdcbench import harness, probes

    planned: list = []
    kept: list = []
    install = probes.Probes.install

    def install_and_watch(self):
        install(self)
        kept.append(self)
        plan_window = hpm.BatchedHPMPlanner.plan_window

        def watched(planner, requests):
            out = plan_window(planner, requests)
            if self._capturing:
                planned.extend(out)
            return out

        hpm.BatchedHPMPlanner.plan_window = watched

    probes.Probes.install = install_and_watch
    rc = harness.main(["--workload", workload, "--seed", "3000000123",
                       "--seconds", "0.1", "--rehearse"],
                      time.perf_counter())
    captured = kept[0].ops or []
    print(json.dumps({"planned": len(planned), "captured": len(captured),
                      "same_ops": [list(o) for o in captured]
                      == [list(o) for o in planned]}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
