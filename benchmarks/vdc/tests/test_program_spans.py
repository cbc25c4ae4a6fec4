"""The readers of the program's own spans and counters, on a hand-made
record set, and with no ``repro.core.telemetry`` to read (an older
checkout)."""
import sys
import types

import pytest

from vdcbench import harness

READERS = ("window_prep_share", "serve_share", "prefetch_apply_share",
           "push_apply_share", "stream_share", "loop_self_share",
           "gc_share", "loop_events")
MS = 1_000_000


def span(name, start_ms, end_ms, parent=None, **meta):
    return types.SimpleNamespace(name=name, start_ns=start_ms * MS,
                                 end_ns=end_ms * MS, parent=parent, job=1,
                                 window=0, meta=meta)


def synthetic():
    """One 10-s job in a 10-s window: 1 s of prep and fold, a 6-s loop
    with 1 s of placement in it, a 0.5-s drain."""
    return [
        span("vdc.sim.job", 0, 10_000, gc_ns=300 * MS, gc_pauses=40),
        span("vdc.engine.window", 100, 9_000, parent=0),
        span("vdc.engine.prep", 100, 600, parent=1),
        span("vdc.hpm.plan", 600, 2_000, parent=1),
        span("vdc.engine.loop", 2_000, 8_000, parent=1, serve_ns=1_000 * MS,
             prefetch_ns=800 * MS, push_ns=1_200 * MS, stream_ns=500 * MS,
             requests=1000, prefetch_events=300, push_events=2000,
             absorbed=10),
        span("vdc.engine.placement", 3_000, 4_000, parent=4),
        span("vdc.engine.fold", 8_000, 8_500, parent=1),
        span("vdc.engine.drain", 9_000, 9_500, parent=0, prefetch_ns=100 * MS,
             push_ns=200 * MS, prefetch_events=5, push_events=7),
    ]


def read(name):
    ctx = types.SimpleNamespace(window_s=10.0, spans=[], counters={},
                                trace=None, plane="/device:TPU:0")
    return harness.load_reader(name)(ctx)


@pytest.fixture
def recorded(monkeypatch):
    recs = synthetic()
    telemetry = types.SimpleNamespace(records=lambda: list(recs))
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", telemetry)
    import repro.core
    monkeypatch.setattr(repro.core, "telemetry", telemetry, raising=False)
    return recs


def test_readers_on_a_synthetic_record(recorded):
    assert read("window_prep_share") == pytest.approx(10.0)
    assert read("serve_share") == pytest.approx(10.0)
    assert read("prefetch_apply_share") == pytest.approx(9.0)
    assert read("push_apply_share") == pytest.approx(14.0)
    assert read("stream_share") == pytest.approx(5.0)
    # 6 s of loop - 3.5 s of timed calls - 1 s of placement
    assert read("loop_self_share") == pytest.approx(15.0)
    assert read("gc_share") == pytest.approx(3.0)
    assert read("loop_events") == 1000 + 300 + 2000 + 5 + 7


def test_readers_without_the_program_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry", raising=False)
    for name in READERS:
        assert read(name) is None, name


def test_readers_with_nothing_recorded(monkeypatch):
    telemetry = types.SimpleNamespace(records=lambda: [])
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", telemetry)
    import repro.core
    monkeypatch.setattr(repro.core, "telemetry", telemetry, raising=False)
    for name in READERS:
        assert read(name) is None, name
