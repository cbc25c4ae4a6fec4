"""Replay-engine benchmark: reference (per-chunk dict/heap) vs vectorized
(array batch-replay) vs interval (interval-algebra presence + sharded
driver) on OOI and GAGE profiles.

Measures end-to-end ``run_strategy`` throughput (requests/second) for every
engine on the same trace/config, interleaving repetitions and keeping the
best time per engine so shared-machine noise cannot bias the ratios.  Each
scenario also cross-checks that all engines produced identical integer
counters — the benchmark doubles as an equivalence audit at full scale.

Writes ``BENCH_engine.json`` at the repo root (schema documented in
``docs/BENCHMARKS.md``).

The ``--full-trace`` mode replays a paper-scale synthetic stream (default
17.9M requests — the OOI trace size) through the windowed streaming path,
one engine per subprocess (clean per-engine peak-RSS high-water), audits a
materialized prefix against the windowed run, and merges a ``full_trace``
row family (``requests`` / ``rps`` / ``peak_rss_mb`` / ``counters_match``)
into the existing ``BENCH_engine.json`` without re-running the matrix.

Usage:
    PYTHONPATH=src python benchmarks/bench_engine.py            # full matrix
    PYTHONPATH=src python benchmarks/bench_engine.py --smoke    # CI quick run
    PYTHONPATH=src python benchmarks/bench_engine.py --engines vector,reference
    PYTHONPATH=src python benchmarks/bench_engine.py --full-trace
    PYTHONPATH=src python benchmarks/bench_engine.py --full-trace 1000000
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import platform
import subprocess
import sys
import time

from repro.core import SimConfig, make_trace, run_strategy
from repro.core.compile_cache import enable_compile_cache
from repro.core.trace import (GAGE_PROFILE, OOI_PROFILE,
                              StreamingRequestSource,
                              StreamingTraceSynthesizer, TraceGenerator,
                              TraceProfile)

ENGINES = ("interval", "vector", "reference")

# --full-trace knobs: the user population is sized so the synthesizer's
# solved duration stays in the months range (dense chunk-key space a few
# million keys — the regime the vector engine's flat arrays are built for),
# while program streams still dominate the request count as in the real
# OOI logs.  All recorded so rows reproduce exactly.
FULL_TRACE_SEED = 12
FULL_TRACE_USERS = 20_000
FULL_TRACE_WINDOW = 131_072
FULL_TRACE_AUDIT = 200_000
FULL_TRACE_DEFAULT = 17_900_000       # paper §V-A1: the OOI trace size

# "ooi_rt" stresses the real-time traffic class (paper Table II: 25.7% of
# OOI volume is real-time polling; here it dominates): many tiny
# single-chunk requests, the flat-cost regime of the serving path.
OOI_RT_PROFILE = dataclasses.replace(
    OOI_PROFILE, name="ooi_rt", n_users=200,
    type_volume_mix=(0.1, 0.8, 0.1))

# The hpm scenarios stress the *prediction* layer (the vectorized engine
# plans the whole op stream through the vmapped ARIMA bank; the reference
# engine predicts online, one padded fit per program request).  Program
# periods are jittered past the near-constant-median fast path (std/median
# > 2%), so every history prediction runs a real ARIMA fit — the regime the
# paper's §IV-A2 predictor operates in on noisy production schedules.
# Population sizes are chosen so the online reference stays benchmarkable.
OOI_ARIMA_PROFILE = dataclasses.replace(
    OOI_PROFILE, name="ooi_arima", n_users=16, human_user_frac=0.25,
    type_volume_mix=(0.85, 0.05, 0.10), period_jitter_frac=0.06,
    duration=7 * 24 * 3600.0)
GAGE_ARIMA_PROFILE = dataclasses.replace(
    GAGE_PROFILE, name="gage_arima", n_users=16, human_user_frac=0.4,
    type_volume_mix=(0.80, 0.05, 0.15), period_jitter_frac=0.08,
    duration=7 * 24 * 3600.0)

PROFILES: dict[str, TraceProfile] = {
    "ooi": OOI_PROFILE, "gage": GAGE_PROFILE, "ooi_rt": OOI_RT_PROFILE,
    "ooi_arima": OOI_ARIMA_PROFILE, "gage_arima": GAGE_ARIMA_PROFILE,
}

# (trace, strategy, chunk_seconds, cache_bytes, trace_scale).
# The cache_only rows are the *serving-bound* set (summarized separately):
# chunk-resolution sweep 3600 s → 60 s, an eviction-thrash cache, the
# streaming-heavy real-time mix, and 2x-scaled traces that amortize fixed
# costs the way full-trace replays (17.9M-77.8M requests) would.
FULL_SCENARIOS = [
    ("ooi", "cache_only", 3600.0, 128 << 30, 1.0),
    ("ooi", "cache_only", 900.0, 128 << 30, 1.0),
    ("ooi", "cache_only", 300.0, 128 << 30, 1.0),
    # fine-chunking regime (one chunk per real-time poll period); the
    # reference replays ~2 orders of magnitude more chunk positions than
    # at 3600 s, so the trace is halved to keep it benchmarkable
    ("ooi", "cache_only", 60.0, 128 << 30, 0.5),
    # eviction-thrash regime: the fused block-over-intervals path has to
    # truncate blocks at eviction pressure and replay the reference's
    # cumulative eviction arithmetic — on both trace profiles
    ("ooi", "cache_only", 3600.0, 8 << 30, 1.0),
    ("gage", "cache_only", 3600.0, 8 << 30, 1.0),
    ("gage", "cache_only", 3600.0, 128 << 30, 1.0),
    ("ooi_rt", "cache_only", 3600.0, 128 << 30, 1.0),
    ("ooi", "cache_only", 3600.0, 128 << 30, 2.0),
    ("ooi_rt", "cache_only", 3600.0, 128 << 30, 2.0),
    ("ooi", "no_cache", 3600.0, 128 << 30, 1.0),
    ("ooi_arima", "hpm", 3600.0, 128 << 30, 1.0),
    ("gage_arima", "hpm", 3600.0, 128 << 30, 1.0),
]

SMOKE_SCENARIOS = [
    ("ooi", "cache_only", 3600.0, 128 << 30, 0.08),
    ("ooi", "cache_only", 120.0, 128 << 30, 0.08),
    # small-cache thrash: exercises the fused path's eviction planning and
    # block truncation under the smoke counter audit
    ("ooi", "cache_only", 3600.0, 1 << 30, 0.08),
    ("gage", "cache_only", 3600.0, 128 << 30, 0.08),
    ("ooi_arima", "hpm", 3600.0, 128 << 30, 0.5),
    # windowed streaming rows: every engine consumes the trace through a
    # StreamingRequestSource, and a materialized run joins the counter
    # audit — any streamed-vs-materialized divergence fails the smoke run
    # non-zero exactly like an engine divergence
    ("ooi", "cache_only", 3600.0, 128 << 30, 0.08, 640),
    ("ooi_arima", "hpm", 3600.0, 128 << 30, 0.5, 640),
    # the two FULL-scale 8 GB thrash rows (same shape as FULL_SCENARIOS):
    # cheap enough for CI because capacity-bound truncation keeps every
    # engine's block small, and they feed the committed-speedup floor
    # guard at the end of main()
    ("ooi", "cache_only", 3600.0, 8 << 30, 1.0),
    ("gage", "cache_only", 3600.0, 8 << 30, 1.0),
]

_SPLITS: dict = {}


def get_split(trace: str, scale: float):
    key = (trace, scale)
    if key not in _SPLITS:
        if trace in ("ooi", "gage"):
            tr = make_trace(trace, seed=0, scale=scale)
        else:
            profile = PROFILES[trace]
            if scale != 1.0:
                profile = dataclasses.replace(
                    profile, n_users=max(8, int(profile.n_users * scale)))
            tr = TraceGenerator(profile, seed=0).generate()
        cut = int(len(tr) * 0.3)
        _SPLITS[key] = (tr[:cut], tr[cut:])
    return _SPLITS[key]


def _counters(res) -> tuple:
    # outcome_totals() folds per-request outcomes for materialized runs and
    # returns the streamed OutcomeAggregate as-is, so the audit covers the
    # byte-split integers on both input paths
    agg = res.outcome_totals()
    return (res.origin_requests, res.prefetch_issued_chunks,
            res.prefetch_used_chunks, res.stream_pushes,
            tuple(sorted((d, s.hits, s.misses, s.evictions,
                          s.inserted_bytes)
                         for d, s in res.cache_stats.items())),
            agg.n, agg.bytes, agg.local_bytes, agg.prefetched_bytes,
            agg.peer_bytes, agg.origin_bytes)


def run_scenario(trace: str, strategy: str, chunk_seconds: float,
                 cache_bytes: int, scale: float, window: int | None = None,
                 engines: list[str] = (), reps: int = 1) -> dict:
    profile = PROFILES[trace]
    train, test = get_split(trace, scale)
    requests = (StreamingRequestSource.from_requests(test, window=window)
                if window else test)
    best: dict[str, float] = {e: float("inf") for e in engines}
    counters: dict[str, tuple] = {}
    evict_ctr: dict[str, dict] = {}
    for _ in range(reps):
        for engine in engines:
            gc.collect()
            cfg = SimConfig(
                stream_rate_bytes_per_s=profile.bytes_per_second_stream,
                cache_bytes=cache_bytes,
                chunk_seconds=chunk_seconds,
            ).calibrate_origin(test)
            t0 = time.perf_counter()
            res = run_strategy(strategy, requests, profile.grid, cfg, train,
                               engine=engine)
            best[engine] = min(best[engine], time.perf_counter() - t0)
            counters[engine] = _counters(res)
            evict_ctr[engine] = dict(plan=res.evict_plan_calls,
                                     trunc=res.block_truncations,
                                     degen=res.degenerate_serves,
                                     phases=res.block_phases,
                                     invict=res.inblock_victims)
    if window:
        # windowed rows additionally audit against a materialized run (the
        # streaming==materialized contract, tests/test_streaming_replay.py)
        cfg = SimConfig(
            stream_rate_bytes_per_s=profile.bytes_per_second_stream,
            cache_bytes=cache_bytes,
            chunk_seconds=chunk_seconds,
        ).calibrate_origin(test)
        res = run_strategy(strategy, test, profile.grid, cfg, train,
                           engine=engines[0])
        counters["materialized"] = _counters(res)
    audit_ref = ("reference" if "reference" in engines
                 else "materialized" if window else None)
    if audit_ref is not None:
        for e, c in counters.items():
            if c != counters[audit_ref]:
                # record the divergence instead of aborting: the row's
                # counters_match flag lands in the JSON (and the artifact),
                # and main() exits non-zero after writing it
                print(f"ENGINE DIVERGENCE in {trace}/{strategy} "
                      f"(chunk={chunk_seconds}s cache={cache_bytes >> 30}G "
                      f"scale={scale} window={window}): {e}={c} != "
                      f"{audit_ref}={counters[audit_ref]}", file=sys.stderr)
    n = len(test)
    row = dict(trace=trace, strategy=strategy, chunk_seconds=chunk_seconds,
               cache_gb=cache_bytes >> 30, trace_scale=scale, n_requests=n,
               serving=strategy == "cache_only",
               counters_match=all(c == counters[engines[0]]
                                  for c in counters.values()))
    if window:
        row["window"] = window
    for e in engines:
        row[f"{e}_rps"] = round(n / best[e], 1)
        row[f"{e}_seconds"] = round(best[e], 3)
        if e != "reference":
            # eviction-path telemetry (deterministic per engine/scenario):
            # visible in smoke rows so plan/truncation-frequency regressions
            # show up without a profiler
            row[f"{e}_evict_ctr"] = evict_ctr[e]
    if "reference" in engines:
        for e in engines:
            if e != "reference":
                row[f"speedup_{e}"] = round(best["reference"] / best[e], 2)
        fastest = [e for e in engines if e != "reference"]
        if fastest:
            row["speedup"] = max(row[f"speedup_{e}"] for e in fastest)
    return row


def _geomean(vals: list[float]) -> float:
    return round(math.prod(vals) ** (1.0 / len(vals)), 2) if vals else 0.0


# ---------------------------------------------------------------------------
# --full-trace: paper-scale streamed replay (one engine per subprocess)
# ---------------------------------------------------------------------------


def _full_trace_worker(engine: str, n_requests: int,
                       trace: str = "ooi") -> None:
    """Subprocess body for one ``--full-trace`` row.

    The timed windowed replay runs first so ``ru_maxrss`` is this engine's
    high-water mark alone (generation + replay, nothing materialized); the
    prefix audit afterwards replays the first ``FULL_TRACE_AUDIT`` requests
    both materialized and windowed on the same engine and config, pinning
    the streaming==materialized counter contract at this scale."""
    import resource

    profile = PROFILES[trace]
    synth = StreamingTraceSynthesizer(profile, seed=FULL_TRACE_SEED,
                                      n_requests=n_requests,
                                      n_users=FULL_TRACE_USERS)
    # calibrate the origin-queue service rate from a prefix, then drop the
    # materialized requests so they do not count against the peak
    cal = synth.materialize(FULL_TRACE_AUDIT)
    cfg = SimConfig(
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
        cache_bytes=128 << 30,
        chunk_seconds=3600.0,
    ).calibrate_origin(cal)
    del cal
    gc.collect()

    t0 = time.perf_counter()
    res = run_strategy("cache_only", synth.source(window=FULL_TRACE_WINDOW),
                       profile.grid, cfg, None, engine=engine)
    seconds = time.perf_counter() - t0
    assert res.total_requests == n_requests, res.total_requests
    peak_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

    prefix = synth.materialize(FULL_TRACE_AUDIT)
    mat = run_strategy("cache_only", prefix, profile.grid, cfg, None,
                       engine=engine)
    st = run_strategy(
        "cache_only",
        StreamingRequestSource.from_requests(prefix,
                                             window=FULL_TRACE_WINDOW // 8),
        profile.grid, cfg, None, engine=engine)
    row = dict(engine=engine, requests=n_requests,
               seconds=round(seconds, 2),
               rps=round(n_requests / seconds, 1),
               peak_rss_mb=round(peak_mb, 1),
               counters_match=_counters(mat) == _counters(st))
    print(json.dumps(row))


def run_full_trace(n_requests: int, engines: list[str],
                   trace: str = "ooi") -> list[dict]:
    """Spawn one worker subprocess per engine and collect their rows."""
    src_dir = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
    env = dict(os.environ)
    env["PYTHONPATH"] = src_dir + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    rows = []
    for engine in engines:
        print(f"full-trace[{trace}]: {engine} x {n_requests:,} requests "
              f"(window={FULL_TRACE_WINDOW}) ...", flush=True)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--_full-trace-worker", engine, "--full-trace",
             str(n_requests), "--full-trace-trace", trace],
            env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stdout)
            sys.stderr.write(proc.stderr)
            raise RuntimeError(f"full-trace worker failed for {engine}")
        row = json.loads(proc.stdout.strip().splitlines()[-1])
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small traces, single rep (CI regression check)")
    ap.add_argument("--engines", default=",".join(ENGINES),
                    help="comma-separated subset of "
                         f"{'/'.join(ENGINES)} (default: all)")
    ap.add_argument("--reps", type=int, default=None,
                    help="repetitions per engine (default: 2 full, 1 smoke)")
    ap.add_argument("--out", default=None,
                    help="output JSON path (default: BENCH_engine.json)")
    ap.add_argument("--full-trace", type=int, nargs="?",
                    const=FULL_TRACE_DEFAULT, default=None, metavar="N",
                    help="replay an N-request synthetic stream (default "
                         f"{FULL_TRACE_DEFAULT:,}, the paper's OOI trace "
                         "size) through the windowed streaming path and "
                         "merge a full_trace row family into the JSON")
    ap.add_argument("--full-trace-trace", dest="full_trace_trace",
                    choices=("ooi", "gage"), default="ooi",
                    help="trace profile for --full-trace rows: ooi (17.9M "
                         "§V-A1 default) or gage (pair with --full-trace "
                         "77800000 for the paper's GAGE trace size)")
    ap.add_argument("--_full-trace-worker", dest="full_trace_worker",
                    default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    engines = [e.strip() for e in args.engines.split(",") if e.strip()]
    unknown = set(engines) - set(ENGINES)
    if unknown:
        ap.error(f"unknown engines: {sorted(unknown)}")
    path = args.out or os.path.join(os.path.dirname(__file__), "..",
                                    "BENCH_engine.json")

    if args.full_trace_worker:
        _full_trace_worker(args.full_trace_worker,
                           args.full_trace or FULL_TRACE_DEFAULT,
                           args.full_trace_trace)
        return

    if args.full_trace is not None:
        # the reference engine replays per chunk position — hours at this
        # scale — so full-trace rows default to the batch engines unless an
        # engine set was given explicitly
        ft_engines = (engines if args.engines != ",".join(ENGINES)
                      else ["interval", "vector"])
        ft_rows = run_full_trace(args.full_trace, ft_engines,
                                 args.full_trace_trace)
        data = {}
        if os.path.exists(path):
            with open(path) as f:
                data = json.load(f)
        # each profile keeps its own row family so an OOI run never
        # clobbers a recorded GAGE row (and vice versa)
        ft_key = ("full_trace" if args.full_trace_trace == "ooi"
                  else f"full_trace_{args.full_trace_trace}")
        data[ft_key] = dict(
            n_requests=args.full_trace, profile=args.full_trace_trace,
            n_users=FULL_TRACE_USERS, seed=FULL_TRACE_SEED,
            window=FULL_TRACE_WINDOW, audit_prefix=FULL_TRACE_AUDIT,
            strategy="cache_only", chunk_seconds=3600.0, cache_gb=128,
            host=dict(machine=platform.machine(), cpus=os.cpu_count()),
            rows=ft_rows)
        with open(path, "w") as f:
            json.dump(data, f, indent=2)
        print(f"wrote {os.path.abspath(path)}")
        bad = [r["engine"] for r in ft_rows if not r["counters_match"]]
        if bad:
            print("FAIL: streamed-vs-materialized prefix audit failed for "
                  f"{', '.join(bad)}", file=sys.stderr)
            sys.exit(1)
        return

    scenarios = SMOKE_SCENARIOS if args.smoke else FULL_SCENARIOS
    reps = args.reps or (1 if args.smoke else 2)
    rows = []
    for sc in scenarios:
        row = run_scenario(*sc, engines=engines, reps=reps)
        rows.append(row)
        print(json.dumps(row))

    out = dict(
        benchmark="replay-engine",
        mode="smoke" if args.smoke else "full",
        engines=engines,
        reps=reps,
        host=dict(machine=platform.machine(),
                  cpus=os.cpu_count()),
        scenarios=rows,
    )
    if "reference" in engines:
        for e in engines:
            if e == "reference":
                continue
            sp = [r[f"speedup_{e}"] for r in rows]
            out[f"speedup_geomean_{e}"] = _geomean(sp)
        sp = [r["speedup"] for r in rows]
        out["speedup_max"] = max(sp)
        out["speedup_min"] = min(sp)
        out["speedup_geomean"] = _geomean(sp)
        # the ROADMAP serving-path target tracks the cache_only rows: the
        # best engine per row (what run_strategy callers would pick for
        # that workload) against the per-chunk reference
        out["serving_speedup_geomean"] = _geomean(
            [r["speedup"] for r in rows if r["serving"]])
        out["all_counters_match"] = all(r["counters_match"] for r in rows)
    prev = {}
    if os.path.exists(path):
        # keep a previously merged full_trace row family across matrix
        # runs; ``prev`` also feeds the committed-speedup floor guard below
        try:
            with open(path) as f:
                prev = json.load(f)
            for k in ("full_trace", "full_trace_gage"):
                if k in prev:
                    out[k] = prev[k]
        except (json.JSONDecodeError, OSError):
            prev = {}
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.abspath(path)}")
    if "reference" in engines:
        print(f"speedup (best engine/row): min {out['speedup_min']}x  "
              f"geomean {out['speedup_geomean']}x  max {out['speedup_max']}x")
        print(f"serving-path geomean: {out['serving_speedup_geomean']}x")
    mismatched = [f"{r['trace']}/{r['strategy']}" for r in rows
                  if not r["counters_match"]]
    if mismatched:
        print(f"FAIL: counter mismatch in {', '.join(mismatched)}",
              file=sys.stderr)
        sys.exit(1)
    # serving-path floor: the flat interval state exists to make the fused
    # block-over-intervals path competitive on coarse chunks, so the smoke
    # run fails if that row falls clearly behind the vector engine (the
    # 0.9 factor is grace for single-rep timing noise)
    if (args.smoke and "reference" in engines and "interval" in engines
            and "vector" in engines):
        coarse = [r for r in rows
                  if r["serving"] and r["chunk_seconds"] >= 3600.0
                  and r["cache_gb"] >= 64 and "window" not in r]
        floor_bad = [f"{r['trace']}@{int(r['chunk_seconds'])}s"
                     for r in coarse
                     if r["speedup_interval"] < 0.9 * r["speedup_vector"]]
        if floor_bad:
            print("FAIL: fused interval path fell below the vector engine "
                  f"on coarse-chunk rows: {', '.join(floor_bad)}",
                  file=sys.stderr)
            sys.exit(1)
    if args.smoke and "reference" in engines and prev.get("mode") == "full":
        # 8 GB thrash floor: the committed full-matrix speedups for the
        # eviction-thrash rows are a regression contract for the eviction
        # planner — fail the smoke run if either row's best-engine speedup
        # falls below 0.9x of the committed value (grace for single-rep
        # timing noise); rows are matched on their full scenario shape
        committed = {(r["trace"], r["chunk_seconds"], r["cache_gb"],
                      r["trace_scale"]): r.get("speedup")
                     for r in prev.get("scenarios", [])}
        thrash_bad = []
        for r in rows:
            if r["cache_gb"] != 8 or "window" in r or "speedup" not in r:
                continue
            floor = committed.get((r["trace"], r["chunk_seconds"],
                                   r["cache_gb"], r["trace_scale"]))
            if floor and r["speedup"] < 0.9 * floor:
                thrash_bad.append(
                    f"{r['trace']}: {r['speedup']}x < 0.9*{floor}x")
        if thrash_bad:
            print("FAIL: 8 GB thrash rows fell below the committed "
                  f"BENCH_engine.json floor: {'; '.join(thrash_bad)}",
                  file=sys.stderr)
            sys.exit(1)


if __name__ == "__main__":
    main()
