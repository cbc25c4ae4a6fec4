"""The replay's own spans and counters (``repro.core.telemetry``).

With no profiler trace running nothing is recorded and no collector hook is
installed; with a CPU trace running every span is in the trace's host
events under the same name and about the same duration, the event loop's
time accumulators ride on its annotation, and the bank-call counter agrees
with a count taken around the bank programs themselves.  Neither changes
what a replay computes.
"""
import collections
import dataclasses
import gc
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import SimConfig, StreamingRequestSource, arima, run_strategy
from repro.core import telemetry
from repro.core.engine import VectorVDCSimulator
from repro.core.streaming import StreamingEngine
from repro.core.trace import OOI_PROFILE, WEEK, TraceGenerator

WINDOW = 512
LOOP_KEYS = ("serve_ns", "prefetch_ns", "push_ns", "stream_ns")


@pytest.fixture(scope="module")
def replay():
    """A one-week OOI hpm trace, streamed in windows: program users whose
    periods jitter by 1% (a few real ARIMA fits; on the CPU each bank call
    puts ~10^5 op events in a profile) and real-time users (stream
    pushes)."""
    profile = dataclasses.replace(
        OOI_PROFILE, name="ooi_hpm", n_users=6, human_user_frac=0.2,
        type_volume_mix=(0.9, 0.05, 0.05), period_jitter_frac=0.01,
        duration=WEEK)
    tr = TraceGenerator(profile, seed=3).generate()
    cut = int(len(tr) * 0.3)
    train, test = tr[:cut], tr[cut:]
    cfg = SimConfig(
        cache_bytes=1 << 30,
        stream_rate_bytes_per_s=profile.bytes_per_second_stream,
    ).calibrate_origin(test)

    def run():
        source = StreamingRequestSource.from_requests(test, window=WINDOW)
        return run_strategy("hpm", source, profile.grid, cfg, train)

    run()                               # compile every bank bucket used
    return run


def profiled(tmp_path, fn):
    """``fn()`` under a CPU profiler trace; its result and the trace's
    ``vdc.*`` host events as ``{name: [(duration_ns, stats), ...]}``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = fn()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = collections.defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("vdc."):
                    events[e.name].append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return out, {k: [ev[1:] for ev in sorted(v, key=lambda ev: ev[0])]
                 for k, v in events.items()}


def test_nothing_recorded_without_a_trace(replay, tmp_path, monkeypatch):
    hooks = []
    loop = VectorVDCSimulator._dyn_loop

    def watched(sim, *args):
        hooks.append(list(gc.callbacks))
        return loop(sim, *args)

    monkeypatch.setattr(VectorVDCSimulator, "_dyn_loop", watched)
    before = list(gc.callbacks)
    telemetry.reset()
    off = replay()
    assert telemetry.records() == []
    assert hooks and all(h == before for h in hooks)
    assert gc.callbacks == before
    # the counters are always on
    counts = telemetry.counters()
    assert counts["requests"] == off.total_requests
    assert counts["push_events"] == off.stream_pushes > 0
    assert counts["bank_calls"] > 0

    on, _ = profiled(tmp_path, replay)
    assert telemetry.records()
    assert gc.callbacks == before
    for f in dataclasses.fields(off):
        assert getattr(on, f.name) == getattr(off, f.name), f.name


def test_spans_match_the_profiler_trace(replay, tmp_path, monkeypatch):
    calls = [0]
    compiled_bank = arima._compiled_bank

    def counted(*key):
        program = compiled_bank(*key)

        def call(rows):
            calls[0] += 1
            return program(rows)

        return call

    monkeypatch.setattr(arima, "_compiled_bank", counted)
    telemetry.reset()
    res, events = profiled(tmp_path, replay)
    recs = telemetry.records()
    names = collections.Counter(r.name for r in recs)
    for name in ("vdc.sim.job", "vdc.delivery.train", "vdc.engine.window",
                 "vdc.engine.prep", "vdc.hpm.plan", "vdc.arima.flush",
                 "vdc.engine.loop", "vdc.engine.fold", "vdc.engine.drain"):
        assert names[name] > 0, name
    assert names["vdc.engine.window"] == -(-res.total_requests // WINDOW)

    # every span is a host event of the same name and about its duration
    seen = collections.Counter()
    for r in sorted(recs, key=lambda r: r.start_ns):
        dur, stats = events[r.name][seen[r.name]]
        seen[r.name] += 1
        assert abs(dur - r.duration_ns) <= max(0.05 * r.duration_ns,
                                               500_000), r.name
        if r.name == "vdc.engine.loop":
            assert {k: stats[k] for k in r.meta} == r.meta
    assert seen == names

    job, = (r for r in recs if r.name == "vdc.sim.job")
    for r in recs:
        assert r.job == job.job
        if r.parent is not None:
            parent = recs[r.parent]
            assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
            assert r.window == parent.window or \
                parent.name == "vdc.sim.job"
        if r.name in ("vdc.engine.loop", "vdc.engine.fold"):
            assert recs[r.parent].name == "vdc.engine.window"
    loops = [r for r in recs if r.name == "vdc.engine.loop"]
    for r in loops:
        assert all(0 <= r.meta[k] <= r.duration_ns for k in LOOP_KEYS)
        assert sum(r.meta[k] for k in LOOP_KEYS) <= r.duration_ns
    assert sum(r.meta["requests"] for r in loops) == res.total_requests
    drained = [r for r in recs if r.name == "vdc.engine.drain"]
    assert sum(r.meta["push_events"] for r in loops + drained) == \
        res.stream_pushes

    flushes = [r for r in recs if r.name == "vdc.arima.flush"]
    assert sum(r.meta["bank_calls"] for r in flushes) == calls[0] > 0
    for r in flushes:
        assert r.meta["bank_rows"] + r.meta["bank_pad_rows"] == \
            r.meta["bank_calls"] * arima.BANK_WIDTH
    assert telemetry.counters()["bank_calls"] == calls[0]
    assert job.meta["gc_pauses"] >= 0 and job.meta["gc_ns"] >= 0


def test_timed_events_match_the_counters(replay, monkeypatch):
    """The counters, derived from heap sizes and totals, equal a count of
    the calls the loop makes."""
    applied = collections.Counter()
    for name in ("_apply_prefetch", "_apply_push"):
        fn = getattr(VectorVDCSimulator, name)

        def counted(sim, *args, _fn=fn, _name=name):
            applied[_name] += 1
            return _fn(sim, *args)

        monkeypatch.setattr(VectorVDCSimulator, name, counted)
    subscribe = StreamingEngine.subscribe

    def counted_subscribe(eng, *args):
        applied["subscribe"] += 1
        return subscribe(eng, *args)

    monkeypatch.setattr(StreamingEngine, "subscribe", counted_subscribe)
    telemetry.reset()
    res = replay()
    counts = telemetry.counters()
    assert counts["prefetch_events"] == applied["_apply_prefetch"] > 0
    assert counts["push_events"] == applied["_apply_push"] > 0
    # nothing unsubscribes in a replay: one DTN tuple built per subscribe
    assert counts["dtn_builds"] == applied["subscribe"] > 0
    assert counts["requests"] == res.total_requests


def test_collector_pauses_are_timed(tmp_path):
    telemetry.reset()
    before = list(gc.callbacks)

    def job():
        with telemetry.job():
            gc.collect()

    profiled(tmp_path, job)
    assert gc.callbacks == before
    job_span, = (r for r in telemetry.records() if r.name == "vdc.sim.job")
    pause, = (r for r in telemetry.records() if r.name == "vdc.gc")
    assert job_span.meta["gc_pauses"] >= 1
    assert 0 < pause.duration_ns <= job_span.meta["gc_ns"]
    assert pause.job == job_span.job


@pytest.mark.parametrize("n", [*arima._BUCKETS, arima.ARIMA().n])
def test_bank_program_is_named(n):
    bank = arima._compiled_bank(n, 2, 1, 1, 200, 0.05)
    rows = jax.ShapeDtypeStruct((arima.BANK_WIDTH, n), jnp.float32)
    assert f"module @jit_arima_bank_n{n} " in bank.lower(rows).as_text()
