"""Bring-up smoke run of the delivery replay on one TPU chip.

    python3 chip_smoke.py

One process from start to end; every input is generated from fixed seeds.
Each phase prints one JSON line:

1. ``device``: JAX must report a TPU, or the script exits non-zero at
   once.  Times one full ``BANK_WIDTH``-row ARIMA bank call per history
   bucket, checks that the result lives on the chip, and reports how far
   the chip's forecasts lie from the CPU backend's on the same rows.
2. ``hpm``: the paper-scale OOI and GAGE traces (scale 1.0, 8 GB caches)
   through ``run_strategy("hpm", engine="vector")``: ARIMA bank, placement
   k-means and host replay together, one line per trace.  The CPU figures
   of the same runs are printed beside the chip's.
3. ``streamed``: the OOI test split replayed as a windowed
   ``StreamingRequestSource`` must give phase 2's integer counters.
4. ``online``: ``hpm`` on the engine benchmark's ``ooi_arima`` profile at
   its smoke size, through the vector engine (batched bank) and the
   reference engine (one padded bank call per forecast), must give
   identical integer counters.

Compile counts and seconds come from JAX's own monitoring events; bank
calls are the program's own counter (``repro.core.telemetry``), which
counts the dispatches of ``ARIMA.batched_forecast``.  The last
line, ``{"ok": true, "device": {...}}``, is printed only when every phase
passed.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import bench_engine  # noqa: E402
from repro.core import (SimConfig, StreamingRequestSource,  # noqa: E402
                        make_trace, run_strategy)
from repro.core import arima, telemetry  # noqa: E402
from repro.core.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.trace import GAGE_PROFILE, OOI_PROFILE  # noqa: E402

SEED = 0
HPM_TRACES = {"ooi": OOI_PROFILE, "gage": GAGE_PROFILE}
HPM_SCALE = 1.0
HPM_CACHE_BYTES = 8 << 30
STREAM_WINDOW = 131_072
ONLINE_PROFILE, ONLINE_SCALE = "ooi_arima", 0.5

# Phase 2 on the XLA CPU backend (same code and seeds; 8-core x86
# container, JAX 0.9.0, warm compile cache), printed beside the chip's
# figures.  The counters may differ on the chip, where the fit's float
# math differs; the script does not fail on that.
CPU_HPM = {
    "ooi": {"recall": 0.3999080882352941, "origin_requests": 31137,
            "bank_calls": 6, "wall_s": 11.073437765000108},
    "gage": {"recall": 0.27299755544299337, "origin_requests": 54442,
             "bank_calls": 10, "wall_s": 6.939146691000133},
}


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


class CompileStats:
    """Compilations seen through JAX's monitoring events.

    ``backend_compile_duration`` fires for every program JAX hands to the
    backend, persistent-cache hits included; ``cache_hits`` fires for the
    hits alone, so requests minus hits is what XLA compiled."""

    def __init__(self):
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, float, int]:
        return self.requests, self.seconds, self.cache_hits

    def since(self, snap: tuple[int, float, int]) -> dict:
        req, sec, hits = snap
        hits = self.cache_hits - hits
        return {"compile_s": self.seconds - sec,
                "compiles": self.requests - req - hits,
                "cache_hits": hits}


def bank_calls() -> int:
    return telemetry.counters().get("bank_calls", 0)


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX reports platform {dev.platform!r}, not a "
                 "TPU; nothing here runs on a CPU fallback")
    return dev


def device_phase(dev: jax.Device, stats: CompileStats, cache_dir: str) -> None:
    """Phase 1: one full bank call per history bucket, on the chip."""
    snap, calls = stats.snapshot(), bank_calls()
    model = arima.ARIMA()
    cpu = jax.devices("cpu")[0]
    rng = np.random.default_rng(SEED)
    bank_s, rel_diff = {}, {}
    for n in (*arima._BUCKETS, model.n):
        rows = rng.normal(3600.0, 400.0, (arima.BANK_WIDTH, n)).astype(
            np.float32)
        model.batched_forecast(list(rows))                    # compile
        t0 = time.perf_counter()
        out = model.batched_forecast(list(rows))   # synced: returns numpy
        bank_s[n] = time.perf_counter() - t0
        check(out.shape == (arima.BANK_WIDTH,) and np.isfinite(out).all(),
              f"bank n={n} gave {out}")
        program = model._bank(n)
        on_chip = program(jnp.asarray(rows))
        check(on_chip.devices() == {dev},
              f"bank n={n} output lives on {on_chip.devices()}")
        on_cpu = np.asarray(program(jax.device_put(rows, cpu)))
        rel = np.abs(np.asarray(on_chip) - on_cpu) / np.abs(on_cpu)
        rel_diff[n] = [float(rel.max()), float(np.median(rel))]
    emit(phase="device", platform=dev.platform, kind=dev.device_kind,
         count=len(jax.devices()), compile_cache=cache_dir,
         bank_call_s=bank_s, bank_vs_cpu_rel_diff_max_median=rel_diff,
         **stats.since(snap), bank_calls=bank_calls() - calls)


def hpm_run(requests, profile, cfg, train, engine, stats):
    snap, calls = stats.snapshot(), bank_calls()
    t0 = time.perf_counter()
    res = run_strategy("hpm", requests, profile.grid, cfg, train,
                       engine=engine)
    wall = time.perf_counter() - t0
    row = {"wall_s": wall, **stats.since(snap),
           "bank_calls": bank_calls() - calls, "recall": res.recall,
           "origin_requests": res.origin_requests}
    return res, row


def hpm_config(profile, test) -> SimConfig:
    return SimConfig(
        cache_bytes=HPM_CACHE_BYTES,
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
    ).calibrate_origin(test)


def hpm_phase(stats: CompileStats, scale: float = HPM_SCALE) -> tuple:
    """Phase 2: paper-scale hpm on OOI and GAGE.  Returns the OOI split and
    its integer counters for phase 3."""
    for name, profile in HPM_TRACES.items():
        t0 = time.perf_counter()
        tr = make_trace(name, seed=SEED, scale=scale)
        split = int(len(tr) * 0.3)
        train, test = tr[:split], tr[split:]
        trace_s = time.perf_counter() - t0
        res, row = hpm_run(test, profile, hpm_config(profile, test), train,
                           "vector", stats)
        counters = bench_engine._counters(res)
        emit(phase="hpm", trace=name, scale=scale, requests=len(test),
             trace_s=trace_s, **row, counters=counters, cpu=CPU_HPM[name])
        if name == "ooi":
            ooi = train, test, counters
    return ooi


def streamed_phase(stats: CompileStats, train, test, counters) -> None:
    """Phase 3: the OOI split streamed in windows == materialized."""
    profile = HPM_TRACES["ooi"]
    source = StreamingRequestSource.from_requests(test, window=STREAM_WINDOW)
    res, row = hpm_run(source, profile, hpm_config(profile, test), train,
                       "vector", stats)
    streamed = bench_engine._counters(res)
    emit(phase="streamed", trace="ooi", window=STREAM_WINDOW,
         requests=res.total_requests, **row,
         counters_match=streamed == counters)
    check(streamed == counters,
          f"streamed counters {streamed} != materialized {counters}")


def online_phase(stats: CompileStats, scale: float = ONLINE_SCALE) -> None:
    """Phase 4: batched (vector) == online (reference) hpm counters."""
    profile = bench_engine.PROFILES[ONLINE_PROFILE]
    train, test = bench_engine.get_split(ONLINE_PROFILE, scale)
    counters, rows = {}, {}
    for engine in ("vector", "reference"):
        cfg = SimConfig(
            stream_rate_bytes_per_s=profile.bytes_per_second_stream,
            cache_bytes=128 << 30, chunk_seconds=3600.0,
        ).calibrate_origin(test)
        res, rows[engine] = hpm_run(test, profile, cfg, train, engine, stats)
        counters[engine] = bench_engine._counters(res)
    match = counters["vector"] == counters["reference"]
    emit(phase="online", trace=ONLINE_PROFILE, scale=scale,
         requests=len(test), **rows, counters_match=match)
    check(match, f"vector counters {counters['vector']} != reference "
                 f"{counters['reference']}")


def main() -> None:
    cache_dir = enable_compile_cache()
    stats = CompileStats()
    dev = require_tpu()
    device_phase(dev, stats, cache_dir)
    streamed_phase(stats, *hpm_phase(stats))
    online_phase(stats)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
