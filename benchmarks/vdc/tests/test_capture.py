"""The check takes a planned job's ops where the planner's windows are
routed (``delivery._route_planned_ops``), not from one planner's method:
for a tiny ``hpm`` job, the op lists captured there are the ones that
``BatchedHPMPlanner.plan_window`` returned, request for request; for a
tiny planned ``md2`` job (``fault_run.py``'s ``--md2-planned``, or the
program's own planner where it has one), they are the ones that the
planner routed.

The job runs in a child process (``python test_capture.py <cell>
[fault_run options]``), since the probes replace attributes of the program
for the life of a process.
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def captured_run(tmp_path, *options: str) -> tuple[dict, dict]:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "gage_vdc_32g.paper", *options],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-2]), json.loads(lines[-1])


def test_hpm_capture_is_the_planners_output(tmp_path):
    run, out = captured_run(tmp_path)
    assert run["correct"], run["checks"]
    assert out["captured"] == out["planned"] > 0
    assert out["same_ops"]


def test_md2_planned_capture_is_the_routed_ops(tmp_path):
    run, out = captured_run(tmp_path, "--strategy", "md2", "--md2-bank",
                            "--md2-planned")
    assert run["correct"], run["checks"]
    assert out["captured"] == out["routed"] > 0
    assert out["same_routed"]


def _routed_as_captured(plans: list) -> list:
    """Per request, what a planner's routed plans hold in the form the
    probes capture: its scheduled ops, and one op of reason ``stream`` for
    each subscription."""
    return [(list(ops), len(subs)) for plan in plans
            for ops, subs in zip(plan.ops, plan.subscriptions)]


def main(workload: str, options: list[str]) -> int:
    """One rehearsed run of ``workload`` under ``fault_run.py``'s
    ``options``; prints, after the harness's own line, how many requests'
    ops the probes captured in the run's checked job, how many the ``hpm``
    planner returned and how many the job's window planner routed, and
    whether each is the same as what was captured."""
    sys.path[:0] = [HERE, os.path.dirname(HERE),
                    os.path.join(os.path.dirname(os.path.dirname(
                        os.path.dirname(HERE))), "src")]
    import fault_run
    from repro.core import delivery, hpm
    from vdcbench import harness, probes

    strategy, rest = fault_run.apply_options(options)
    if rest:
        raise SystemExit(f"unknown options {rest!r}")
    fault_run.set_size(harness, "tiny", strategy)
    planned: list = []
    routed: list = []
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

        make_prefetcher = delivery.make_prefetcher

        def watched_prefetcher(*args, **kw):
            pf = make_prefetcher(*args, **kw)
            make_planner = getattr(pf, "planner", None)
            if make_planner is None:
                return pf

            def watched_planner():
                window_planner = make_planner()
                plan_window = window_planner.plan_window

                def watched_window(requests):
                    plan = plan_window(requests)
                    if self._capturing:
                        routed.append(plan)
                    return plan

                window_planner.plan_window = watched_window
                return window_planner

            pf.planner = watched_planner
            return pf

        delivery.make_prefetcher = watched_prefetcher

    probes.Probes.install = install_and_watch
    rc = harness.main(["--workload", workload, "--seed", "3000000123",
                       "--seconds", "0.1", "--rehearse"],
                      time.perf_counter())
    captured = kept[0].ops or []
    as_routed = [([o for o in ops if o.reason != "stream"],
                  sum(o.reason == "stream" for o in ops)) for ops in captured]
    routed_ops = _routed_as_captured(routed)
    print(json.dumps({"planned": len(planned), "captured": len(captured),
                      "routed": len(routed_ops),
                      "same_ops": [list(o) for o in captured]
                      == [list(o) for o in planned],
                      "same_routed": as_routed == routed_ops}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2:]))
