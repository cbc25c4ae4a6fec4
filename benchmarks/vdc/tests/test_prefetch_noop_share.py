"""The ``prefetch_noop_share`` reader on a hand-made record set, and on
records of a program that does not count ``prefetch_noop`` (an older
checkout)."""
import sys
import types

import pytest

from vdcbench import harness

MS = 1_000_000


def span(name, start_ms, end_ms, **meta):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 end_ns=end_ms * MS, parent=None, job=1,
                                 window=0, meta=meta)


def read(recs, monkeypatch):
    telemetry = types.SimpleNamespace(records=lambda: list(recs))
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", telemetry)
    import repro.core
    monkeypatch.setattr(repro.core, "telemetry", telemetry, raising=False)
    ctx = types.SimpleNamespace(window_s=10.0, spans=[], counters={},
                                trace=None, plane="/device:TPU:0")
    return harness.load_reader("prefetch_noop_share")(ctx)


def test_share_of_a_recorded_meta(monkeypatch):
    recs = [
        span("vdc.sim.job", 0, 10_000),
        span("vdc.engine.loop", 2_000, 5_000, requests=1000,
             prefetch_events=300, prefetch_noop=270, push_events=20),
        span("vdc.engine.loop", 5_000, 8_000, requests=1000,
             prefetch_events=90, prefetch_noop=81, push_events=0),
        span("vdc.engine.drain", 9_000, 9_500, prefetch_events=10,
             prefetch_noop=4, push_events=7),
    ]
    assert read(recs, monkeypatch) == pytest.approx(100.0 * 355 / 400)


def test_none_without_the_counter(monkeypatch):
    recs = [span("vdc.engine.loop", 2_000, 5_000, requests=1000,
                 prefetch_events=300, push_events=20),
            span("vdc.engine.drain", 9_000, 9_500, prefetch_events=10,
                 push_events=7)]
    assert read(recs, monkeypatch) is None


def test_none_without_prefetch_ops(monkeypatch):
    recs = [span("vdc.engine.loop", 2_000, 5_000, requests=1000,
                 prefetch_events=0, prefetch_noop=0, push_events=20)]
    assert read(recs, monkeypatch) is None


def test_none_without_the_program_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry", raising=False)
    ctx = types.SimpleNamespace(window_s=10.0)
    assert harness.load_reader("prefetch_noop_share")(ctx) is None
