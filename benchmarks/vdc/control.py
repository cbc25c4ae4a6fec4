"""The correctness check's control: the plain reference in the program's
place, its ARIMA bank computed in bfloat16 (the precision below the
configuration's float32), compared with the float32 reference by the
benchmark's own comparison.  Every seed must come out not correct.

    python3 benchmarks/vdc/control.py --workload <cell> --seeds 1 2 3 \
        [--rehearse]

Prints one JSON line per seed with the compared numbers, the verdict and
the platform it ran on (the readings that set the limits come from the
chip).  The benchmark's runs never run this.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from vdcbench import correct, harness  # noqa: E402
from vdcbench.ref import simulator as ref_sim  # noqa: E402


def control(workload: str, seed: int, rehearse: bool = False,
            dtype: str = "bfloat16", windows: int | None = None) -> dict:
    """One seed of the control; ``windows`` shortens the checked prefix to
    that many stream windows of the cell's own size."""
    _, _, spec = harness.load_cell(workload)
    if rehearse:
        spec = harness.rehearse_spec(spec)
    if windows is not None:
        spec["check"]["windows"] = windows
    profile, train, test = harness.make_trace(spec, seed % (1 << 64))
    cfg = ref_sim.SimConfig(
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
        origin_latency_s=harness.origin_latency(
            test, spec["sim"].get("n_service_procs", 10),
            spec["sim"].get("traffic_scale", 1.0), spec["origin_utilization"]),
        **spec["sim"])
    t0 = time.perf_counter()
    ref_out, ref_ops, ref_ctr = correct.reference(spec, test, train,
                                                  profile.grid, cfg)
    t1 = time.perf_counter()
    low_out, low_ops, low_ctr = correct.reference(
        spec, test, train, profile.grid, cfg, arima_dtype=dtype)
    t2 = time.perf_counter()
    verdict = correct.compare(correct.columns_of(low_out), low_ops, low_ctr,
                              ref_out, ref_ops, ref_ctr)
    return {"workload": workload, "seed": seed, "dtype": dtype,
            "requests": len(ref_out), "correct": verdict.correct,
            "checks": verdict.as_dict(), "reference_s": t1 - t0,
            "control_s": t2 - t1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks/vdc/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    platform = jax.devices()[0].platform
    for seed in args.seeds:
        row = control(args.workload, seed, args.rehearse)
        print(json.dumps({**row, "platform": platform}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
