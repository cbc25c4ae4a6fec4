"""Batched-vs-online prediction-layer equivalence (the contract that lets
the vectorized engine pre-plan the whole hpm op stream).

Three layers of pinning:

- **ARIMA bank** (hypothesis): ``ARIMA.batched_forecast`` returns *bitwise*
  the same floats as per-series ``forecast_next`` across ragged history
  lengths — the <4-point fallback, history bucketing, the fixed-width bank
  padding and batch grouping all included.  Likewise
  ``predict_next_timestamps`` vs the scalar ``predict_next_timestamp``
  (median fast path, <2-point fallback and the ARIMA branch).
- **Two-phase planner** (seeded traces): ``BatchedHPMPlanner.plan`` equals
  the online ``observe`` stream op-for-op on OOI + GAGE and on a
  jittered-period trace that forces real ARIMA fits through the bank.
- **Satellite semantics**: d≥2 un-differencing against a NumPy reference
  on a quadratic-trend series, and the association-rule issue timestamp
  ``ts_i + offset·(ts_{i+1} − ts_i)`` with ``ts_{i+1} = ts_i + (ts_i −
  ts_{i−1})`` and ``tr_{i+1} = tr_i``.
"""
import dataclasses

import numpy as np
import pytest

try:        # property tests need hypothesis; everything else runs without it
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAS_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAS_HYPOTHESIS = False

from repro.core import make_trace
from repro.core.arima import (ARIMA, ARIMAOrder, BANK_WIDTH, _integrate,
                              predict_next_timestamp, predict_next_timestamps)
from repro.core.hpm import (PREFETCH_OFFSET, BatchedHPMPlanner,
                            HybridPrefetcher, build_rule_transactions)
from repro.core.trace import OOI_PROFILE, WEEK, Request, TraceGenerator

# small model: every history bucket stays cheap under hypothesis
_MODEL = ARIMA(n=16, steps=60)


# ---------------------------------------------------------------------------
# ARIMA bank vs scalar
# ---------------------------------------------------------------------------

if HAS_HYPOTHESIS:
    # width=32 bounds must be exact float32 values (1e-3 is not)
    finite = st.floats(min_value=float(np.float32(1e-3)), max_value=1e6,
                       allow_nan=False, allow_infinity=False, width=32)

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.lists(finite, min_size=0, max_size=24), min_size=1,
                    max_size=6))
    def test_batched_forecast_matches_scalar(series_list):
        batched = _MODEL.batched_forecast(series_list)
        scalar = [_MODEL.forecast_next(np.asarray(s, np.float32))
                  for s in series_list]
        assert batched.tolist() == scalar

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.lists(finite, min_size=0, max_size=30), min_size=1,
                    max_size=5),
           st.floats(min_value=0.0, max_value=1e5, allow_nan=False))
    def test_predict_next_timestamps_matches_scalar(gap_lists, t0):
        # strictly increasing timestamp series from positive gaps; also
        # covers the <2-point fallback and (via tiny lists) the <4 fallback
        series = [np.cumsum([t0] + gaps) for gaps in gap_lists]
        batched = predict_next_timestamps(series, _MODEL)
        scalar = [predict_next_timestamp(ts, _MODEL) for ts in series]
        assert batched.tolist() == scalar
else:                                                 # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_batched_forecast_matches_scalar():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_predict_next_timestamps_matches_scalar():
        pass


def test_predict_fast_path_near_constant_gaps():
    """Near-constant inter-arrivals take the median fast path (no fit) in
    both modes and agree exactly."""
    ts = np.cumsum([100.0] + [3600.0, 3600.2, 3599.9, 3600.1] * 10)
    out = predict_next_timestamps([ts], _MODEL)
    assert out[0] == predict_next_timestamp(ts, _MODEL)
    gaps = np.diff(ts)
    med = float(np.median(gaps))
    assert out[0] == pytest.approx(ts[-1] + med, rel=1e-12)


def test_bank_opt_out_uses_scalar_program():
    """bank=False (latency-sensitive consumers outside the equivalence
    contract, e.g. the serving scheduler) dispatches the single-series
    program; fallbacks behave identically and batched == per-series."""
    m = ARIMA(n=16, steps=60, bank=False)
    assert m.forecast_next(np.array([], np.float32)) == 0.0
    assert m.forecast_next(np.array([5.0, 7.0], np.float32)) == 7.0
    series = [np.linspace(10.0, 40.0, 12, dtype=np.float32),
              np.array([3.0], np.float32)]
    out = m.batched_forecast(series)
    assert out.tolist() == [m.forecast_next(s) for s in series]
    assert np.isfinite(out).all()


def test_bank_rows_independent_of_batch_composition():
    """The fixed-width bank computes each row independently: a series'
    forecast does not depend on what else (or how much) is in the batch.
    This is what makes scalar==batched bitwise and the planner exact."""
    rng = np.random.default_rng(0)
    rows = [rng.normal(3600.0, 400.0, size=20).astype(np.float32)
            for _ in range(BANK_WIDTH + 3)]   # forces a padded second batch
    full = _MODEL.batched_forecast(rows)
    alone = [_MODEL.forecast_next(r) for r in rows]
    pair = _MODEL.batched_forecast([rows[5], rows[BANK_WIDTH + 1]])
    assert full.tolist() == alone
    assert pair[0] == alone[5] and pair[1] == alone[BANK_WIDTH + 1]


# ---------------------------------------------------------------------------
# d >= 2 un-differencing (satellite: was a no-op)
# ---------------------------------------------------------------------------


def test_integrate_matches_numpy_reference():
    """_integrate applies f^(k) = tails[k] + f^(k+1) from level d-1 to 0."""
    rng = np.random.default_rng(1)
    for d in (0, 1, 2, 3):
        tails = [float(x) for x in rng.normal(size=d)]
        fy = 0.37
        expect = fy
        for k in reversed(range(d)):        # NumPy-free reference recurrence
            expect = tails[k] + expect
        assert _integrate(fy, tails) == pytest.approx(expect, rel=1e-12)


def test_d2_quadratic_trend_forecast():
    """On a quadratic trend the second difference is constant, so a d=2
    ARIMA must recover the exact quadratic extrapolation
    ``y[-1] + (y[-1] - y[-2]) + c2`` (NumPy reference).  The pre-fix code
    integrated only one level and missed the trend slope."""
    t = np.arange(40, dtype=np.float64)
    y = 3.0 + 2.0 * t + 0.5 * t * t
    model = ARIMA(order=ARIMAOrder(p=1, d=2, q=0), n=32)
    forecast = model.forecast_next(y.astype(np.float32))
    c2 = float(np.diff(y, n=2)[-1])
    reference = y[-1] + (y[-1] - y[-2]) + c2
    assert forecast == pytest.approx(reference, rel=1e-2)
    # the buggy single-level integration could not exceed a linear step
    assert forecast > y[-1] + (y[-1] - y[-2]) * 0.99


# ---------------------------------------------------------------------------
# association-rule issue timestamp (satellite: next_ts was dead)
# ---------------------------------------------------------------------------


def test_rules_issue_at_offset_of_predicted_gap():
    txs = [[1, 2]] * 30                      # rule 1 -> 2, confidence 1.0
    pf = HybridPrefetcher(rule_transactions=txs)
    t1, t2, t3 = 0.0, WEEK + 10.0, WEEK + 100.0
    reqs = [Request(t1, 7, 1, 0.0, 50.0, 100, 0),
            Request(t2, 7, 3, 10.0, 60.0, 100, 0),
            Request(t3, 7, 4, 20.0, 70.0, 100, 0)]
    for r in reqs[:2]:
        pf.observe(r)
    assert pf.classification(7) == "human"
    ops = pf.observe(reqs[2])
    assert [op.obj for op in ops] == [2]
    op = ops[0]
    # ts_{i+1} = ts_i + (ts_i - ts_{i-1}); issued at the offset point
    next_ts = t3 + (t3 - t2)
    assert op.issue_ts == pytest.approx(
        t3 + PREFETCH_OFFSET * (next_ts - t3), rel=1e-12)
    # tr_{i+1} = tr_i
    assert (op.tr_start, op.tr_end) == (20.0, 70.0)
    assert op.reason == "rules"


# ---------------------------------------------------------------------------
# two-phase planner vs online observe (op-for-op)
# ---------------------------------------------------------------------------


def _assert_plan_equals_observe(test_reqs, train_reqs):
    txs = build_rule_transactions(train_reqs) if train_reqs else None
    online = HybridPrefetcher(rule_transactions=txs)
    planner = BatchedHPMPlanner(HybridPrefetcher(rule_transactions=txs))
    planned = planner.plan_window(test_reqs)
    n_ops = 0
    for i, r in enumerate(test_reqs):
        observed = online.observe(r)
        assert list(planned[i]) == observed, f"op stream diverges at {i}"
        n_ops += len(observed)
    assert n_ops > 0, "degenerate trace: no ops to compare"
    return planned


@pytest.mark.parametrize("trace", ["ooi", "gage"])
def test_planner_matches_observe_seeded(trace):
    tr = make_trace(trace, seed=7, scale=0.035)
    cut = int(len(tr) * 0.3)
    _assert_plan_equals_observe(tr[cut:], tr[:cut])


# ---------------------------------------------------------------------------
# window-split invariance (the streaming replay contract)
# ---------------------------------------------------------------------------


def _windowed_ops(test_reqs, cuts, txs):
    """Feed ``test_reqs`` through a stateful planner in windows delimited by
    ``cuts`` (sorted interior indices) and return the concatenated
    per-request op lists."""
    planner = BatchedHPMPlanner(HybridPrefetcher(rule_transactions=txs))
    out: list = []
    lo = 0
    for hi in list(cuts) + [len(test_reqs)]:
        out.extend(planner.plan_window(test_reqs[lo:hi]))
        lo = hi
    return out


def _arima_fit_trace():
    profile = dataclasses.replace(
        OOI_PROFILE, name="ooi_arima", n_users=6, human_user_frac=0.2,
        type_volume_mix=(0.9, 0.05, 0.05), period_jitter_frac=0.06,
        duration=WEEK)
    tr = TraceGenerator(profile, seed=3).generate()
    cut = int(len(tr) * 0.3)
    return tr[cut:], tr[:cut]


def test_plan_window_invariant_under_any_split():
    """Any window-boundary placement — width 1, whole-trace, or random cut
    points — leaves the op stream bitwise identical to the online observe
    reference.  Classification state is per-user-subsequence (windows
    preserve order) and bank rows are batch-composition independent
    (``test_bank_rows_independent_of_batch_composition``), so splits cannot
    change a single op.  This is the prediction half of the streaming
    replay exactness argument (``tests/test_streaming_replay.py``)."""
    import random

    test_reqs, train_reqs = _arima_fit_trace()
    txs = build_rule_transactions(train_reqs)
    online = HybridPrefetcher(rule_transactions=txs)
    reference = [list(online.observe(r)) for r in test_reqs]
    assert sum(map(len, reference)) > 0, "degenerate trace: no ops"
    n = len(test_reqs)
    splits = [list(range(1, n)), []]            # width 1, whole-trace
    rng = random.Random(20260808)               # derandomized property draws
    for _ in range(4):
        k = rng.randint(1, 12)
        splits.append(sorted(rng.sample(range(1, n), k)))
    for cuts in splits:
        got = [list(ops) for ops in _windowed_ops(test_reqs, cuts, txs)]
        assert got == reference, f"op stream diverges for cuts={cuts[:8]}..."


def test_plan_window_split_matches_whole_plan_seeded():
    """On the seeded OOI trace a random two-window split must equal the
    single-shot plan (which itself equals observe, pinned above)."""
    tr = make_trace("ooi", seed=7, scale=0.035)
    cut = int(len(tr) * 0.3)
    test_reqs, train_reqs = tr[cut:], tr[:cut]
    txs = build_rule_transactions(train_reqs)
    whole = BatchedHPMPlanner(
        HybridPrefetcher(rule_transactions=txs)).plan_window(test_reqs)
    mid = len(test_reqs) // 3
    split = _windowed_ops(test_reqs, [mid], txs)
    assert [list(ops) for ops in whole] == [list(ops) for ops in split]


def test_planner_matches_observe_with_arima_fits():
    """Jittered program periods (std/median > 2%) defeat the median fast
    path, so every history prediction goes through a real fit — the planner
    through the vmapped bank, observe through padded batch-of-one calls.
    Exact equality here is what pins the fixed-width-bank design."""
    profile = dataclasses.replace(
        OOI_PROFILE, name="ooi_arima", n_users=6, human_user_frac=0.2,
        type_volume_mix=(0.9, 0.05, 0.05), period_jitter_frac=0.06,
        duration=WEEK)
    tr = TraceGenerator(profile, seed=3).generate()
    cut = int(len(tr) * 0.3)
    planned = _assert_plan_equals_observe(tr[cut:], tr[:cut])
    # make sure the scenario actually exercised the bank
    n_history = sum(1 for ops in planned for op in ops
                    if op.reason == "history")
    assert n_history > 50
