"""The reductions from trace and spans to metrics, on hand-made inputs and
on a small trace recorded on a TPU v5e (``data/recorded.xplane.pb``: the
``vdc.*`` annotations of one job around two bank calls and one Lloyd
call)."""
import os
import types

import pytest

from vdcbench import devtrace, harness, layers

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "recorded.xplane.pb")
PLANE = "/device:TPU:0"


def ctx(spans=(), trace=None, window_s=10.0, bank_calls=0):
    return types.SimpleNamespace(window_s=window_s, spans=list(spans),
                                 counters={"bank_calls": bank_calls},
                                 trace=trace, plane=PLANE)


def read(name, c):
    return harness.load_reader(name)(c)


def test_every_metric_has_a_reader():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    c = ctx()
    c.setup_s, c.requests = 12.5, 1000
    for m in bench["end_to_end"] + bench["per_layer"]:
        harness.load_reader(m["name"])(c)
    assert read("replay_rps", c) == pytest.approx(100.0)
    assert read("setup_s", c) == 12.5


def test_union_merges_and_clips():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (6, 9)], 1, 8) == \
        [[1, 3], [5, 8]]
    assert devtrace.union([(3, 3), (4, 2)], 0, 10) == []


def test_span_shares():
    spans = [("train", 0.0, 1.0), ("plan", 2.0, 4.0), ("bank", 2.5, 3.0),
             ("bank", 3.5, 4.0), ("placement", 5.0, 5.5)]
    c = ctx(spans, bank_calls=2)
    assert read("train_share", c) == pytest.approx(10.0)
    assert read("bank_call_share", c) == pytest.approx(10.0)
    assert read("plan_self_share", c) == pytest.approx(10.0)
    assert read("placement_share", c) == pytest.approx(5.0)
    # the window minus every layer span: 10 - (1 + 2 + 0.5) seconds
    assert read("engine_rest_share", c) == pytest.approx(65.0)
    assert read("bank_calls", c) == 2.0
    assert layers.seconds_within(c, "bank", "plan") == pytest.approx(1.0)


def test_readers_return_nothing_without_input():
    c = ctx()
    for name in ("train_share", "plan_self_share", "bank_calls",
                 "bank_call_share", "placement_share", "engine_rest_share",
                 "device_idle_share"):
        assert read(name, c) is None, name


def synthetic_trace():
    ms = 1_000_000
    return {
        "window": [0, 100 * ms],
        "device": {PLANE: [["jit_a", 10 * ms, 10 * ms],
                           ["jit_b", 15 * ms, 10 * ms],
                           ["jit_a", 90 * ms, 20 * ms]]},
        "host": [["vdc.window", 0, 100 * ms], ["vdc.job", 0, 100 * ms],
                 ["vdc.plan", 30 * ms, 40 * ms],
                 ["vdc.bank", 50 * ms, 5 * ms]],
    }


def test_idle_share_and_breakdown():
    tr = synthetic_trace()
    # busy: [10, 25) and [90, 100) of a 100 ms window
    assert read("device_idle_share", ctx(trace=tr)) == pytest.approx(75.0)
    b = devtrace.breakdown(tr, PLANE)
    assert b["device_ops"][0] == ["jit_a", pytest.approx(0.02)]
    assert b["device_ops"][1] == ["jit_b", pytest.approx(0.01)]
    gaps = b["idle_gaps"]
    assert gaps[0] == ["plan", pytest.approx(0.065)]    # [25, 90): plan at 57.5
    assert gaps[1] == ["engine", pytest.approx(0.010)]  # [0, 10)
    assert devtrace.host_label(tr, 52 * 1_000_000) == "bank"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in tests/data")
def test_recorded_trace():
    tr = devtrace.extract(RECORDED)
    assert tr["window"] is not None
    assert PLANE in tr["device"] and tr["device"][PLANE]
    lo, hi = tr["window"]
    busy = devtrace.busy_ns(tr, PLANE)
    assert 0 < busy < hi - lo
    idle = read("device_idle_share", ctx(trace=tr))
    assert 0.0 < idle < 100.0
    b = devtrace.breakdown(tr, PLANE)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    total_idle = sum(e - s for s, e in devtrace.gaps(tr, PLANE))
    assert total_idle + busy == pytest.approx(hi - lo)
    labels = {label for label, _ in b["idle_gaps"]}
    assert labels <= {"engine", "train", "plan", "bank", "placement"}
    # two bank calls and one Lloyd call ran inside the recorded window
    names = [n for n, _ in b["device_ops"]]
    assert names[0].startswith("jit__lambda") and \
        any(n.startswith("jit_lloyd") for n in names)
    assert len(tr["device"][PLANE]) == 3
