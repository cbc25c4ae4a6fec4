"""One run of one cell: set-up, the measured window of back-to-back replay
jobs, the metric readers, and the correctness check against the plain
reference.

Everything that belongs to one cell comes from data: the cell's entry in
``BENCHMARK.json``, its configuration file (``configs/<config>.json``), its
traffic mix (``traffic/<traffic>.json``) and one reader per metric
(``metrics/<metric>.py``), each found by its name.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
import traceback
import types

import numpy as np

from . import correct, devtrace, tracegen
from .counters import counters
from .ref import simulator as ref_sim

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))

# --rehearse: the same path on the CPU at a tiny size
REHEARSE_USER_DIVISOR = 10
REHEARSE_MIN_USERS = 24
REHEARSE_WINDOW = 4096


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="benchmarks/vdc/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a tiny size; no device metric")
    return ap.parse_args(argv)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict]:
    """``(bench, cell, spec)``: the benchmark file, the cell's entry and the
    merged run specification (configuration, then traffic overrides)."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"vdc bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, conf["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic",
                                     cell["traffic"] + ".json"))
    spec = dict(config)
    for k, v in traffic.items():
        if isinstance(v, dict):
            spec[k] = {**config.get(k, {}), **v}
        elif k not in ("name", "why"):
            spec[k] = v
    return bench, cell, spec


def cell_metrics(bench: dict, cell: dict, key: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[key]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("vdc_metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rehearse_spec(spec: dict) -> dict:
    spec = json.loads(json.dumps(spec))
    p = spec["profile"]
    p["n_users"] = max(REHEARSE_MIN_USERS, p["n_users"] // REHEARSE_USER_DIVISOR)
    spec["stream_window"] = min(spec["stream_window"], REHEARSE_WINDOW)
    spec["check"]["windows"] = 1
    return spec


def origin_latency(requests, n_procs: int, traffic_scale: float,
                   utilization: float) -> float:
    """Origin service time that puts the origin queue at ``utilization``
    when every request reaches it (the program's ``calibrate_origin``
    rule, computed here for the reference)."""
    span = max(1.0, requests[-1].ts - requests[0].ts)
    rate = len(requests) / span * traffic_scale
    return utilization * n_procs / rate


def make_trace(spec: dict, seed: int):
    """``(profile, train, test)``: the cell's trace, drawn from
    ``tracegen.SHAPE_SEED`` and renamed by ``seed``, split."""
    profile = tracegen.profile_from_dict(spec["profile"])
    shape = tracegen.TraceGenerator(profile, seed=tracegen.SHAPE_SEED).generate()
    reqs = tracegen.relabel(shape, profile, seed)
    split = int(len(reqs) * spec["train_frac"])
    return profile, reqs[:split], reqs[split:]


def main(argv, t_start: float) -> int:
    args = parse_args(argv)
    bench, cell, spec = load_cell(args.workload)
    if args.rehearse:
        spec = rehearse_spec(spec)
    seed = args.seed % (1 << 64)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"vdc bench: the program is not at {src}")
    sys.path.insert(0, src)
    if args.trace:
        devtrace.drop_op_trace_points()
    if not args.rehearse:
        # the persistent compile cache lives at a fixed path in the checkout
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    from .chipwatch import CompileStats, require_tpu
    from .probes import Probes

    if args.rehearse:
        devs = jax.devices()[:1]
    else:
        devs = require_tpu(cell["chips"])
    stats = CompileStats()
    probes = Probes(spans=bool(args.trace))
    probes.install()

    from repro.core import arima as p_arima
    from repro.core.kmeans import kmeans as p_kmeans
    from repro.core.simulator import SimConfig, run_strategy
    from repro.core.trace import ObjectGrid, Request, RequestList, \
        StreamingRequestSource

    profile, train, test = make_trace(spec, seed)
    p_train = RequestList(Request(*dataclasses.astuple(r)) for r in train)
    p_test = RequestList(Request(*dataclasses.astuple(r)) for r in test)
    tr_bounds = (min(r.tr_start for r in test), max(r.tr_end for r in test))
    grid = ObjectGrid(profile.grid.n_types, profile.grid.n_locs)
    base_cfg = SimConfig(
        stream_rate_bytes_per_s=profile.bytes_per_second_stream, **spec["sim"],
    ).calibrate_origin(p_test, target_utilization=spec["origin_utilization"])

    class WindowedSource(StreamingRequestSource):
        """The replay split in fixed windows, all of them or the first
        ``max_windows``."""

        def __init__(self, max_windows=None):
            super().__init__(lambda: iter(p_test), window=spec["stream_window"],
                             n_requests=len(p_test), tr_bounds=tr_bounds)
            self.max_windows = max_windows
            self.handed = 0

        def windows(self):
            for i, w in enumerate(super().windows()):
                if self.max_windows is not None and i >= self.max_windows:
                    return
                self.handed += len(w)
                yield w

    def job(source):
        return run_strategy(spec["strategy"], source, grid,
                            dataclasses.replace(base_cfg), p_train,
                            engine=spec["engine"])

    # warm-up: every bank bucket, the placement Lloyd shape, one window
    model = p_arima.ARIMA()
    rng = np.random.default_rng(0)
    for n in (*p_arima._BUCKETS, model.n):
        model.batched_forecast([rng.normal(3600.0, 400.0, n)])
    p_kmeans(rng.integers(0, 30, (5000, 3)).astype(np.float32), 4)
    job(WindowedSource(max_windows=1))

    tracer = None
    if args.trace:
        tracer = tempfile.TemporaryDirectory(prefix="vdc_trace_")
        jax.profiler.start_trace(tracer.name,
                                 profiler_options=devtrace.profile_options())

    # the measured window: whole replay jobs back to back; once the time is
    # up no job starts, and the one in progress runs to its end and counts
    probes.bank_calls = 0
    probes.spans.clear()
    probes.capture(True)
    snap = stats.snapshot()
    attempted = failed = 0
    job_s = []
    first_job = None
    t_open = time.perf_counter()
    setup_s = t_open - t_start
    deadline = t_open + args.seconds
    with jax.profiler.TraceAnnotation("vdc.window"):
        while time.perf_counter() < deadline:
            source = WindowedSource()
            t_job = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("vdc.job"):
                    res = job(source)
                if first_job is None:
                    first_job = counters(res)
            except Exception:
                traceback.print_exc()
                failed += source.handed
            attempted += source.handed
            job_s.append(time.perf_counter() - t_job)
            probes.capture(False)       # the first job is the one checked
    t_close = time.perf_counter()
    window_s = t_close - t_open
    in_window = stats.since(snap)

    mem = devs[0].memory_stats() or {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}

    ctx = types.SimpleNamespace(
        setup_s=setup_s, window_s=window_s, requests=attempted - failed,
        jobs=len(job_s), spans=list(probes.spans),
        counters={"bank_calls": probes.bank_calls}, trace=None,
        plane=f"/device:TPU:{devs[0].id}")
    breakdown = None
    if tracer is not None:
        jax.profiler.stop_trace()
        ctx.trace = devtrace.extract(devtrace.find_xplane(tracer.name))
        tracer.cleanup()
        if ctx.trace["window"] is not None and ctx.plane in ctx.trace["device"]:
            lo, hi = ctx.trace["window"]
            device["busy_s"] = devtrace.busy_ns(ctx.trace, ctx.plane) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            breakdown = devtrace.breakdown(ctx.trace, ctx.plane)

    metrics = {}
    for m in cell_metrics(bench, cell, "per_layer" if args.trace else "end_to_end"):
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # correctness: the window's first job against the plain reference
    ref_cfg = ref_sim.SimConfig(
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
        origin_latency_s=origin_latency(
            test, spec["sim"].get("n_service_procs", 10),
            spec["sim"].get("traffic_scale", 1.0), spec["origin_utilization"]),
        **spec["sim"])
    verdict = correct.check(probes, spec, test, train, profile.grid, ref_cfg)
    ok = verdict.correct and failed == 0
    for line in verdict.lines():
        print(line, file=sys.stderr)
    if in_window["programs"]:
        print(f"vdc bench: {in_window['programs']} programs compiled or "
              "loaded inside the measured window", file=sys.stderr)
        if not args.rehearse:
            return 3
    # a rehearsal's numbers come from the CPU: read, never reported
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": {} if args.rehearse else metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if args.rehearse:
        result["rehearse"] = {"jobs": len(job_s), "window_s": window_s,
                              "bank_calls": probes.bank_calls}
    result["job_s"] = job_s
    result["job_counters"] = first_job
    result["checks"] = verdict.as_dict()
    print(json.dumps(result), flush=True)
    return 0
