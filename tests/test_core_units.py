"""Unit tests for core/ components: classify, ARIMA, FP-Growth, K-Means,
caches, placement, streaming."""
import itertools

import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (ARIMA, LFUCache, LRUCache, MarkovPredictor,
                        RulePredictor, StreamingEngine, association_rules,
                        chunks_for_range, frequent_itemsets, kmeans,
                        predict_next_timestamp, select_hub)
from repro.core.classify import (classify_request_type, fresh_duplicate_bytes)
from repro.core.trace import HOUR, Request


def _mk(ts, obj=0, uid=0, s=None, e=None, size=100):
    s = ts - HOUR if s is None else s
    e = ts if e is None else e
    return Request(ts, uid, obj, s, e, size, 0)


# ---------------------------------------------------------------- classify

class TestRequestType:
    def test_regular(self):
        reqs = [_mk(i * HOUR, s=(i - 1) * HOUR, e=i * HOUR) for i in range(1, 20)]
        t, period = classify_request_type(reqs)
        assert t == "regular"
        assert period == pytest.approx(HOUR)

    def test_realtime(self):
        reqs = [_mk(i * 60.0, s=(i - 1) * 60.0, e=i * 60.0) for i in range(1, 50)]
        t, _ = classify_request_type(reqs)
        assert t == "realtime"

    def test_overlapping(self):
        reqs = [_mk(i * HOUR, s=max(0, i - 24) * HOUR, e=i * HOUR)
                for i in range(1, 30)]
        t, _ = classify_request_type(reqs)
        assert t == "overlapping"


class TestFreshDuplicate:
    def test_disjoint_all_fresh(self):
        reqs = [_mk(i * HOUR, s=(i - 1) * HOUR, e=i * HOUR) for i in range(1, 10)]
        fresh, dup = fresh_duplicate_bytes(reqs)
        assert dup == 0 and fresh > 0

    def test_full_repeat_duplicate(self):
        reqs = [_mk(float(i), s=0.0, e=HOUR, size=1000) for i in range(5)]
        fresh, dup = fresh_duplicate_bytes(reqs)
        assert fresh == 1000
        assert dup == 4000

    def test_moving_day_window(self):
        # past-24h every hour: 23/24 duplicate
        reqs = [_mk(i * HOUR, s=(i - 24) * HOUR, e=i * HOUR, size=24_000)
                for i in range(24, 100)]
        fresh, dup = fresh_duplicate_bytes(reqs)
        frac = dup / (fresh + dup)
        assert frac == pytest.approx(23 / 24, abs=0.02)


# ------------------------------------------------------------------ ARIMA

class TestARIMA:
    def test_constant_series(self):
        ts = np.arange(100) * 3600.0
        pred = predict_next_timestamp(ts)
        assert pred == pytest.approx(ts[-1] + 3600.0, rel=0.01)

    def test_linear_trend_gaps(self):
        # gaps grow linearly: 100, 110, 120, ... ARIMA(2,1,1) should track
        gaps = 100.0 + 10.0 * np.arange(60)
        ts = np.concatenate([[0.0], np.cumsum(gaps)])
        pred = predict_next_timestamp(ts)
        expected_gap = gaps[-1] + 10.0
        got_gap = pred - ts[-1]
        assert got_gap == pytest.approx(expected_gap, rel=0.25)

    def test_noisy_periodic(self):
        rng = np.random.default_rng(0)
        gaps = 3600.0 + rng.normal(0, 200.0, size=80)
        ts = np.concatenate([[0.0], np.cumsum(gaps)])
        pred = predict_next_timestamp(ts)
        assert pred - ts[-1] == pytest.approx(3600.0, rel=0.2)

    def test_forecast_finite(self):
        m = ARIMA()
        out = m.forecast_next(np.array([1.0, 2.0, 1.5, 3.0, 2.5] * 10))
        assert np.isfinite(out)


# --------------------------------------------------------------- FP-Growth

class TestFPGrowth:
    def test_known_example(self):
        txs = [
            ["a", "b"], ["b", "c", "d"], ["a", "c", "d", "e"],
            ["a", "d", "e"], ["a", "b", "c"], ["a", "b", "c", "d"],
            ["a"], ["a", "b", "c"],
        ]
        out = frequent_itemsets(txs, min_support=3)
        assert out[frozenset(["a"])] == 7
        assert out[frozenset(["a", "b"])] == 4
        assert out[frozenset(["c", "d"])] == 3

    def test_against_bruteforce(self):
        rng = np.random.default_rng(1)
        items = list("abcdef")
        txs = [
            [i for i in items if rng.random() < 0.4] or ["a"]
            for _ in range(60)
        ]
        min_sup = 8
        got = frequent_itemsets(txs, min_sup)
        # brute force
        want = {}
        for r in range(1, len(items) + 1):
            for combo in itertools.combinations(items, r):
                sup = sum(1 for t in txs if set(combo) <= set(t))
                if sup >= min_sup:
                    want[frozenset(combo)] = sup
        assert got == want

    def test_rules_confidence(self):
        txs = [["a", "b"]] * 9 + [["a"]]
        out = frequent_itemsets(txs, 2)
        rules = association_rules(out, 0.5)
        ab = [r for r in rules if r.antecedent == frozenset(["a"])]
        assert ab and ab[0].confidence == pytest.approx(0.9)

    def test_predictor_topn(self):
        txs = [["x", "y", "z"]] * 20 + [["x", "q"]] * 5
        pred = RulePredictor(txs, min_support=3, min_confidence=0.3)
        out = pred.predict(["x"], top_n=2)
        assert "y" in out or "z" in out

    @given(st.lists(
        st.lists(st.integers(0, 5), min_size=1, max_size=4),
        min_size=1, max_size=30,
    ))
    @settings(max_examples=25, deadline=None)
    def test_property_support_monotone(self, txs):
        """Support of any superset <= support of subset (anti-monotone)."""
        out = frequent_itemsets(txs, min_support=1)
        for itemset, sup in out.items():
            for item in itemset:
                sub = itemset - {item}
                if sub:
                    assert out[sub] >= sup


# ------------------------------------------------------------------ caches

class TestCaches:
    def test_lru_eviction_order(self):
        c = LRUCache(300)
        c.insert("a", 100); c.insert("b", 100); c.insert("c", 100)
        assert c.lookup("a", 100)          # a becomes MRU
        c.insert("d", 100)                 # evicts b (LRU)
        assert not c.contains("b")
        assert c.contains("a") and c.contains("c") and c.contains("d")

    def test_lfu_eviction(self):
        c = LFUCache(300)
        c.insert("a", 100); c.insert("b", 100); c.insert("c", 100)
        c.lookup("a", 1); c.lookup("a", 1); c.lookup("b", 1)
        c.insert("d", 100)                 # evicts c (freq 1)
        assert not c.contains("c")
        assert c.contains("a") and c.contains("b") and c.contains("d")

    def test_oversized_object_rejected(self):
        c = LRUCache(100)
        c.insert("big", 200)
        assert not c.contains("big")
        assert c.used == 0

    @given(st.lists(st.tuples(st.integers(0, 20), st.integers(1, 50)),
                    min_size=1, max_size=200),
           st.sampled_from(["lru", "lfu"]))
    @settings(max_examples=40, deadline=None)
    def test_property_capacity_invariant(self, ops, policy):
        from repro.core import make_cache
        c = make_cache(policy, 120)
        for key, size in ops:
            if not c.lookup(key, size):
                c.insert(key, size)
            assert 0 <= c.used <= c.capacity
            # used == sum of resident sizes
        assert c.used <= c.capacity

    def test_chunks_for_range(self):
        ck = chunks_for_range(7, 0.0, 3 * HOUR)
        assert ck == [(7, 0), (7, 1), (7, 2)]
        ck = chunks_for_range(7, 1800.0, 5400.0)
        assert ck == [(7, 0), (7, 1)]
        assert chunks_for_range(7, 5.0, 5.0) == []


# ------------------------------------------------------------------ kmeans

class TestKMeans:
    def test_two_clear_clusters(self):
        rng = np.random.default_rng(0)
        a = rng.normal(0, 0.1, (30, 2))
        b = rng.normal(5, 0.1, (30, 2))
        x = np.concatenate([a, b])
        centers, assign, _ = kmeans(x, 2, seed=0)
        assert len(set(assign[:30])) == 1
        assert len(set(assign[30:])) == 1
        assert assign[0] != assign[-1]

    def test_k_larger_than_n(self):
        x = np.array([[0.0, 0.0], [1.0, 1.0]])
        centers, assign, _ = kmeans(x, 5)
        assert centers.shape[0] == 2


# ------------------------------------------------------------- placement

class TestPlacement:
    def test_select_hub_prefers_throughput(self):
        bw = np.array([
            [0, 10, 10, 10],
            [10, 0, 40, 40],     # DTN1 has the best peer links
            [10, 5, 0, 5],
            [10, 5, 5, 0],
        ], dtype=float)
        hub = select_hub([1, 2, 3], bw, {1: 0.5, 2: 0.5, 3: 0.5},
                         {1: 1.0, 2: 1.0, 3: 1.0})
        assert hub == 1

    def test_select_hub_frequency_tiebreak(self):
        bw = np.ones((3, 3)) * 10
        hub = select_hub([1, 2], bw, {1: 0.5, 2: 0.5}, {1: 0.1, 2: 10.0})
        assert hub == 2


# ------------------------------------------------------------- streaming

class TestStreaming:
    def test_absorb_after_subscribe(self):
        eng = StreamingEngine()
        eng.subscribe(user_id=1, dtn=2, obj=7, period=60.0, now=0.0)
        r = _mk(120.0, obj=7, uid=1)
        assert eng.absorb(r)
        r2 = _mk(120.0, obj=8, uid=1)
        assert not eng.absorb(r2)

    def test_push_combining(self):
        eng = StreamingEngine()
        eng.subscribe(1, 2, obj=7, period=60.0, now=0.0)
        eng.subscribe(2, 3, obj=7, period=60.0, now=0.0)
        pushes = eng.pushes_until(180.0)
        # 3 intervals elapsed -> 3 pushes, each to BOTH dtns (combined)
        assert len(pushes) == 3
        assert all(p.dtns == (2, 3) for p in pushes)

    def test_markov_predictor(self):
        from repro.core.trace import ObjectGrid
        grid = ObjectGrid(n_types=1, n_locs=8)
        # access path cycles over locations 0 -> 1 -> 2 -> 0
        reqs = [_mk(float(i), obj=i % 3, uid=0) for i in range(30)]
        m = MarkovPredictor(grid).fit(reqs)
        nxt = m.predict_next_objs(_mk(100.0, obj=0, uid=0), top_n=1)
        assert nxt == [1]   # loc 0 -> loc 1, obj 1 most popular there


# ------------------------------------------------- peer-fetch resolution


from repro.core.delivery import (PeerFetchRange, coalesce_peer_ranges,
                                 select_peer_sources)


def _ref_peer_choice(bw_to_dtn, holders):
    """Brute-force §IV-D spec: iterate DTNs ascending keeping strict
    bandwidth improvements (so ties resolve to the lowest DTN id), accept
    iff the winning peer link strictly beats the origin link."""
    n = holders.shape[1]
    src = np.zeros(n, np.int64)
    acc = np.zeros(n, np.bool_)
    for c in range(n):
        best, best_bw = 0, 0.0
        for d in range(holders.shape[0]):
            if holders[d, c] and bw_to_dtn[d] > best_bw:
                best, best_bw = d, bw_to_dtn[d]
        src[c] = best
        acc[c] = best_bw > 0.0 and bw_to_dtn[best] > bw_to_dtn[0]
    return src, acc


def _chunk_decisions(draw_rows):
    """Normalize drawn rows into per-chunk (req_pos, keys, src) decisions in
    the order the replay engines emit runs: req_pos non-decreasing, keys
    strictly increasing within a request, src per chunk."""
    rows = sorted(set(draw_rows))
    req = np.array([r for r, _, _ in rows], np.int64)
    keys = np.array([k for _, k, _ in rows], np.int64)
    src = np.array([s for _, _, s in rows], np.int64)
    return req, keys, src


def _coalesce_chunks(req, keys, src, dtn):
    """``coalesce_peer_ranges`` over one-chunk runs into one DTN."""
    return coalesce_peer_ranges(req, np.full(len(req), dtn), src, keys,
                                keys + 1)


class TestPeerResolution:
    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 40),
                              st.integers(1, 3)),
                    min_size=1, max_size=80))
    @settings(max_examples=80, deadline=None)
    def test_property_coalesce_covers_and_merges(self, rows):
        req, keys, src = _chunk_decisions(rows)
        out = _coalesce_chunks(req, keys, src, dtn=4)
        # exact cover: every input chunk in exactly one range, nothing else
        got = sorted((r.req_pos, k, r.src)
                     for r in out for k in range(r.key_lo, r.key_hi))
        assert got == sorted(zip(req.tolist(), keys.tolist(), src.tolist()))
        assert all(r.key_lo < r.key_hi and r.dtn == 4 for r in out)
        # maximality: no two emitted ranges are still mergeable
        for a, b in zip(out, out[1:]):
            assert not (a.req_pos == b.req_pos and a.src == b.src
                        and a.key_hi == b.key_lo)

    @given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 40),
                              st.integers(1, 3)),
                    min_size=1, max_size=80))
    @settings(max_examples=80, deadline=None)
    def test_property_coalesce_idempotent(self, rows):
        req, keys, src = _chunk_decisions(rows)
        out = _coalesce_chunks(req, keys, src, dtn=2)
        # re-expanding the ranges and re-coalescing is a fixed point
        req2 = np.array([r.req_pos for r in out
                         for _ in range(r.key_lo, r.key_hi)], np.int64)
        keys2 = np.array([k for r in out
                          for k in range(r.key_lo, r.key_hi)], np.int64)
        src2 = np.array([r.src for r in out
                         for _ in range(r.key_lo, r.key_hi)], np.int64)
        assert _coalesce_chunks(req2, keys2, src2, dtn=2) == out

    @given(st.integers(0, 2**64 - 1))
    @settings(max_examples=120, deadline=None)
    def test_property_select_peer_sources_matches_reference(self, s):
        rng = np.random.default_rng(s)
        n_dtn, n_chunks = 7, int(rng.integers(1, 24))
        # coarse bandwidth levels force frequent exact ties (§IV-D
        # tie-break: max bandwidth, lowest DTN id) and dead links
        bw = rng.choice([0.0, 2.0, 8.0, 8.0, 25.0], size=n_dtn)
        holders = rng.random((n_dtn, n_chunks)) < 0.4
        holders[0] = False                # caller clears origin + self rows
        holders[3] = False
        src, acc = select_peer_sources(bw, holders)
        ref_src, ref_acc = _ref_peer_choice(bw, holders)
        np.testing.assert_array_equal(acc, ref_acc)
        # src is only meaningful where accepted
        np.testing.assert_array_equal(src[acc], ref_src[acc])

    def test_select_peer_sources_tiebreak_lowest_id(self):
        # two peers at identical bandwidth hold the same chunk: the lower
        # DTN id must win (reference iterates ascending keeping strict
        # improvements only)
        bw = np.array([8.0, 25.0, 25.0, 0.0])
        holders = np.zeros((4, 1), np.bool_)
        holders[1, 0] = holders[2, 0] = True
        src, acc = select_peer_sources(bw, holders)
        assert acc[0] and src[0] == 1

    def test_select_peer_sources_origin_tie_rejected(self):
        # a peer exactly matching the origin link is NOT accepted (strict
        # improvement required by §IV-D)
        bw = np.array([25.0, 25.0, 8.0])
        holders = np.zeros((3, 1), np.bool_)
        holders[1, 0] = True
        _, acc = select_peer_sources(bw, holders)
        assert not acc[0]
