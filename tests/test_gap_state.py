"""The batched planner's running gap statistics (``hpm._PlannedUser``)
against the one definition of the fast-path decision, ``_gap_stats``.

- Seeded random request sequences mixing appends, duplicate timestamps,
  history trims past 200 timestamps and out-of-order inserts: after every
  step the unique view and gap list are those of the classification
  state, and ``gap_stats`` equals ``_gap_stats(gaps)`` bit for bit.
- A knife-edge series, ``std/med`` within 1e-12 of 0.02, takes the exact
  fallback (the planner's ``gap_exact`` counter counts it) and the planner
  still emits the online op stream.
- On a seeded trace every program-request decision is counted and the
  fallback stays rare.
"""
import math
import random

import pytest

from repro.core import make_trace, telemetry
from repro.core.arima import _gap_stats
from repro.core.hpm import (BatchedHPMPlanner, HybridPrefetcher,
                            _PlannedUser, build_rule_transactions)
from repro.core.trace import Request


def _request(ts, obj=1, user=0):
    return Request(ts=ts, user_id=user, obj=obj, tr_start=ts - 600.0,
                   tr_end=ts, size_bytes=1000, continent=0)


def _random_stream(rng, n):
    """Timestamps of one user: mostly near-periodic appends (some series
    fast, some not), with duplicates and out-of-order arrivals."""
    period = rng.choice([300.0, 3600.0, 86400.0])
    jitter = rng.choice([0.0, 1e-4, 0.005, 0.0199, 0.0201, 0.05, 0.5])
    t = 1.0e6 * rng.random()
    out = []
    for _ in range(n):
        u = rng.random()
        if u < 0.08 and out:
            out.append(out[-1])                           # duplicate latest
        elif u < 0.14 and out:
            out.append(rng.choice(out))                   # duplicate older
        elif u < 0.20 and out:
            out.append(out[-1] - period * rng.random())   # out of order
        else:
            t += period * (1.0 + jitter * rng.uniform(-1.0, 1.0))
            out.append(t)
    return out


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_running_stats_equal_gap_stats_every_step(seed):
    rng = random.Random(20261019 + seed)
    u = _PlannedUser()
    exact = 0
    n_trims = n_out_of_order = n_dups = 0
    for ts in _random_stream(rng, 700):
        prev_len = len(u.st.timestamps)
        latest = u.uniq[-1] if u.uniq else None
        n_dups += ts in u.uniq
        n_out_of_order += latest is not None and ts < latest
        u.observe(_request(ts, obj=rng.randrange(3)))
        n_trims += len(u.st.timestamps) != prev_len + 1
        assert u.uniq == sorted(set(u.st.timestamps))
        assert u.gaps == [b - a for a, b in zip(u.uniq, u.uniq[1:])]
        assert u.gsorted == sorted(u.gaps)
        if len(u.uniq) < 4:
            continue
        med, max_gap, fast, fell_back = u.gap_stats()
        assert (med, max_gap, fast) == _gap_stats(u.gaps)
        # a repeated call reuses the decision and does not fall back again
        assert u.gap_stats() == (med, max_gap, fast, False)
        exact += fell_back
    assert n_trims >= 5 and n_out_of_order >= 20 and n_dups >= 20
    assert exact <= 2


def test_random_streams_reach_both_decisions():
    seen = set()
    for seed in range(1, 7):
        rng = random.Random(20261019 + seed)
        u = _PlannedUser()
        for ts in _random_stream(rng, 700):
            u.observe(_request(ts))
            if len(u.uniq) >= 4:
                seen.add(u.gap_stats()[2])
    assert seen == {True, False}


def _knife_edge_gaps(base, k, rel):
    """2k+1 gaps, k at ``base − a``, one at ``base``, k at ``base + a``,
    interleaved, with ``a`` set so that ``std/med`` is ``0.02·(1 + rel)``
    in real arithmetic."""
    n = 2 * k + 1
    a = 0.02 * base * math.sqrt(n / (2 * k)) * (1 + rel)
    gaps = [base]
    for _ in range(k):
        gaps += [base - a, base + a]
    return gaps


def _std_over_med(g):
    gs = sorted(g)
    med = gs[len(gs) // 2]
    mean = sum(g) / len(g)
    return (sum((x - mean) ** 2 for x in g) / len(g)) ** 0.5 / med


# in these the running sums alone put var − th on the wrong side of 0, or
# off it where the exact path reads a tie, for one of the two signs
KNIFE_EDGES = [(3600.0, 30, -1e-13), (3600.0, 30, 1e-13),
               (86400.0, 10, -1e-13), (86400.0, 10, 1e-13)]


@pytest.mark.parametrize("base,k,rel", KNIFE_EDGES)
def test_knife_edge_takes_the_exact_fallback(base, k, rel):
    u = _PlannedUser()
    t = 5000.0
    u.observe(_request(t))
    for g in _knife_edge_gaps(base, k, rel):
        t += g
        u.observe(_request(t))
    assert abs(_std_over_med(u.gaps) - 0.02) < 1e-12
    med, max_gap, fast, fell_back = u.gap_stats()
    assert fell_back
    assert (med, max_gap, fast) == _gap_stats(u.gaps)


def test_planner_counts_the_knife_edge_and_matches_observe():
    """A program user whose last request sees the knife-edge gap list: the
    planner's ``gap_exact`` counter counts the fallback, and the op stream
    still equals the online ``observe`` stream."""
    t = 5000.0
    reqs = [_request(t, obj=7)]
    for g in _knife_edge_gaps(3600.0, 30, 1e-13):
        t += g
        reqs.append(_request(t, obj=7))
    telemetry.reset()
    planned = BatchedHPMPlanner(HybridPrefetcher()).plan_window(reqs)
    counts = telemetry.counters()
    online = HybridPrefetcher()
    assert [list(ops) for ops in planned] == [online.observe(r) for r in reqs]
    assert online.classification(0) == "program"
    assert counts["gap_exact"] >= 1
    # every request from the classifying one on, once 4 unique timestamps
    # are held, is a decision
    assert counts["gap_decisions"] == sum(1 for ops in planned if ops)


@pytest.mark.parametrize("trace", ["ooi", "gage"])
def test_seeded_trace_counts_decisions(trace):
    tr = make_trace(trace, seed=7, scale=0.035)
    cut = int(len(tr) * 0.3)
    txs = build_rule_transactions(tr[:cut])
    telemetry.reset()
    BatchedHPMPlanner(HybridPrefetcher(rule_transactions=txs)).plan_window(
        tr[cut:])
    counts = telemetry.counters()
    assert counts["gap_decisions"] > 100
    assert counts["gap_exact"] <= counts["gap_decisions"] // 1000
