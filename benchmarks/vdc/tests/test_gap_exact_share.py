"""The ``gap_exact_share`` reader on a hand-made record set, and on
records of a program that does not count ``gap_decisions`` (an older
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
    return harness.load_reader("gap_exact_share")(ctx)


def test_share_of_a_recorded_meta(monkeypatch):
    recs = [
        span("vdc.sim.job", 0, 10_000),
        span("vdc.hpm.plan", 1_000, 2_000, gap_decisions=30_000,
             gap_exact=3),
        span("vdc.arima.flush", 1_500, 1_600, bank_calls=4),
        span("vdc.hpm.plan", 5_000, 6_000, gap_decisions=10_000,
             gap_exact=1),
        span("vdc.engine.loop", 6_000, 9_000, requests=1000,
             prefetch_events=300, prefetch_noop=270),
    ]
    assert read(recs, monkeypatch) == pytest.approx(100.0 * 4 / 40_000)


def test_zero_when_nothing_falls_back(monkeypatch):
    recs = [span("vdc.hpm.plan", 1_000, 2_000, gap_decisions=500,
                 gap_exact=0)]
    assert read(recs, monkeypatch) == 0.0


def test_none_without_the_counter(monkeypatch):
    recs = [span("vdc.hpm.plan", 1_000, 2_000),
            span("vdc.arima.flush", 1_500, 1_600, bank_calls=4)]
    assert read(recs, monkeypatch) is None


def test_none_without_decisions(monkeypatch):
    recs = [span("vdc.hpm.plan", 1_000, 2_000, gap_decisions=0,
                 gap_exact=0)]
    assert read(recs, monkeypatch) is None


def test_none_without_the_program_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry", raising=False)
    ctx = types.SimpleNamespace(window_s=10.0)
    assert harness.load_reader("gap_exact_share")(ctx) is None
