"""Replay a calibrated OOI trace through the simulated VDC and compare all
five delivery strategies — the paper's §V in one script.

    PYTHONPATH=src python examples/delivery_replay.py [--trace gage]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.core import SimConfig, make_trace, run_strategy
from repro.core.compile_cache import enable_compile_cache
from repro.core.trace import GAGE_PROFILE, OOI_PROFILE


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default="ooi", choices=["ooi", "gage"])
    ap.add_argument("--scale", type=float, default=0.06)
    ap.add_argument("--cache-mb", type=int, default=1024)
    ap.add_argument("--engine", default="vector",
                    choices=["vector", "interval", "reference"],
                    help="replay engine (vector = array batch-replay, "
                         "interval = interval-algebra presence tracking, "
                         "reference = per-chunk dict/heap baseline)")
    args = ap.parse_args()

    profile = OOI_PROFILE if args.trace == "ooi" else GAGE_PROFILE
    tr = make_trace(args.trace, seed=0, scale=args.scale)
    split = int(len(tr) * 0.3)
    train, test = tr[:split], tr[split:]
    cfg = SimConfig(
        cache_bytes=args.cache_mb << 20,
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
    ).calibrate_origin(test)
    print(f"{args.trace}: {len(test)} requests, cache {args.cache_mb} MB, "
          f"engine {args.engine}")
    print(f"{'strategy':12s} {'thr Mbps':>12s} {'latency s':>10s} "
          f"{'recall':>7s} {'origin':>7s} {'local%':>7s}")
    for strat in ("no_cache", "cache_only", "md1", "md2", "hpm"):
        t0 = time.time()
        res = run_strategy(strat, test, profile.grid, cfg, train,
                           engine=args.engine)
        c, p = res.local_access_frac
        print(f"{strat:12s} {res.mean_throughput_mbps:12.1f} "
              f"{res.mean_latency_s:10.2f} {res.recall:7.3f} "
              f"{res.normalized_origin_requests:7.3f} {(c + p) * 100:6.1f}% "
              f"({time.time() - t0:.1f}s)")


if __name__ == "__main__":
    main()
