"""Unit contract of the interval-algebra cache layer (ISSUE 3 tentpole).

:class:`repro.core.cache.IntervalLRUState` must reproduce the reference
:class:`repro.core.cache.LRUCache` chunk for chunk — hit/miss decisions,
eviction order and every counter — while holding presence, sizes and
recency as sorted disjoint ``[start, end)`` intervals.  These tests pin the
named edge cases (zero-length/adjacent ranges, merge-on-insert, eviction
splitting an interval, the full-cache boundary) plus the engine-side
interval utilities (peer-fetch ranges, peer selection).  Engine-level
counter equality on seeded traces lives in ``test_engine_equivalence.py``.
"""
import random

import numpy as np
import pytest

from repro.core.cache import IntervalLRUState, LRUCache
from repro.core.delivery import (PeerFetchRange, coalesce_peer_ranges,
                                 select_peer_sources)
from repro.core.interval_store import FlatIntervalState


def ref_serve(cache: LRUCache, lo: int, hi: int, size: int) -> int:
    """The reference simulator's per-chunk cache interaction for one
    request in the static path: lookup every chunk, then insert every
    miss."""
    missing, nh = [], 0
    for k in range(lo, hi):
        if cache.lookup(k, size):
            nh += 1
        else:
            missing.append(k)
    for k in missing:
        cache.insert(k, size)
    return nh


def keys_of(state: IntervalLRUState) -> list[int]:
    return [k for s, e in state.intervals() for k in range(s, e)]


# ---------------------------------------------------------------------------
# named edge cases
# ---------------------------------------------------------------------------


def test_zero_length_range_is_a_noop():
    st = IntervalLRUState(100)
    assert st.serve(0, 5, 5, 10) == 0
    assert st.lookup_touch(0, 7, 7, 10) == (0, ())
    st.check_invariants()
    assert st.intervals() == []
    assert (st.hits, st.misses, st.used) == (0, 0, 0)


def test_adjacent_ranges_merge_on_insert():
    st = IntervalLRUState(1000)
    st.serve(0, 0, 3, 1)          # miss-insert [0, 3)
    st.serve(0, 3, 6, 1)          # adjacent miss-insert [3, 6)
    st.check_invariants()
    assert st.intervals() == [(0, 6)]            # merged coverage
    assert st.coverage_runs(0, 0, 10) == [(0, 6)]
    # and a spanning request is one full hit across the merged run
    nh, miss = st.lookup_touch(0, 0, 6, 1)
    assert nh == 6 and not miss


def test_merge_on_insert_fills_interior_gap():
    st = IntervalLRUState(1000)
    st.serve(0, 0, 2, 1)
    st.serve(0, 4, 6, 1)
    assert st.intervals() == [(0, 2), (4, 6)]
    st.serve(0, 2, 4, 1)          # fills the hole
    st.check_invariants()
    assert st.intervals() == [(0, 6)]


def test_eviction_splits_an_interval():
    # capacity 4 chunks of size 1; one contiguous insert, then re-touch the
    # middle so the edges are the LRU victims: evicting them must split the
    # stored interval, exactly like the per-chunk reference
    ref = LRUCache(4)
    st = IntervalLRUState(4)
    assert ref_serve(ref, 0, 4, 1) == st.serve(0, 0, 4, 1) == 0
    assert ref_serve(ref, 1, 3, 1) == st.serve(0, 1, 3, 1) == 2
    assert ref_serve(ref, 10, 12, 1) == st.serve(0, 10, 12, 1) == 0
    st.check_invariants()
    assert keys_of(st) == sorted(ref._od.keys()) == [1, 2, 10, 11]
    assert st.intervals() == [(1, 3), (10, 12)]  # [0,4) was split
    assert st.evictions == ref.stats.evictions == 2


def test_full_cache_boundary():
    # exactly-full cache: the next single-chunk insert evicts exactly one
    ref = LRUCache(6)
    st = IntervalLRUState(6)
    ref_serve(ref, 0, 3, 2)
    st.serve(0, 0, 3, 2)
    assert st.used == st.capacity == 6
    ref_serve(ref, 5, 6, 2)
    st.serve(0, 5, 6, 2)
    st.check_invariants()
    assert st.used == 6
    assert st.evictions == ref.stats.evictions == 1
    assert keys_of(st) == sorted(ref._od.keys()) == [1, 2, 5]


def test_oversized_chunk_is_skipped_not_evicted():
    # reference insert(): a chunk larger than the whole cache is silently
    # dropped and must not evict anything
    ref = LRUCache(10)
    st = IntervalLRUState(10)
    ref_serve(ref, 0, 5, 2)
    st.serve(0, 0, 5, 2)
    ref_serve(ref, 7, 8, 11)
    st.serve(0, 7, 8, 11)
    st.check_invariants()
    assert st.evictions == ref.stats.evictions == 0
    assert keys_of(st) == sorted(ref._od.keys())
    assert (st.misses, st.miss_bytes) == (ref.stats.misses,
                                          ref.stats.miss_bytes)


def test_eviction_inside_one_request_self_evicts_in_order():
    # a request larger than the cache evicts its own oldest chunks while
    # inserting the newest — reference order must be preserved
    ref = LRUCache(3)
    st = IntervalLRUState(3)
    ref_serve(ref, 0, 5, 1)
    st.serve(0, 0, 5, 1)
    st.check_invariants()
    assert keys_of(st) == sorted(ref._od.keys()) == [2, 3, 4]
    assert st.evictions == ref.stats.evictions == 2


# ---------------------------------------------------------------------------
# randomized chunk-for-chunk equivalence (incl. the peer-partitioned flow)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(25))
def test_matches_reference_randomized(seed):
    rng = random.Random(seed)
    cap = rng.choice([23, 37, 50, 200, 1000])
    ref = LRUCache(cap)
    st = IntervalLRUState(cap)
    for _ in range(120):
        obj = rng.randrange(2)
        lo = obj * 1000 + rng.randrange(0, 60)
        hi = lo + rng.randrange(0, 12)
        size = rng.choice([1, 2, 5, 13, 60])
        assert ref_serve(ref, lo, hi, size) == st.serve(obj, lo, hi, size)
        st.check_invariants()
        assert keys_of(st) == sorted(ref._od.keys())
        s = ref.stats
        assert (s.hits, s.misses, s.hit_bytes, s.miss_bytes, s.evictions,
                s.inserted_bytes) == \
               (st.hits, st.misses, st.hit_bytes, st.miss_bytes,
                st.evictions, st.inserted_bytes)


def _runs_from(keys):
    out = []
    for k in sorted(keys):
        if out and out[-1][1] == k:
            out[-1] = (out[-1][0], k + 1)
        else:
            out.append((k, k + 1))
    return out


@pytest.mark.parametrize("seed", range(15))
def test_partitioned_insert_order_matches_reference(seed):
    """The interval engine's sweep inserts peer-fetched ranges before
    origin ranges (the reference's ``_serve`` order); eviction decisions
    must track that order exactly."""
    rng = random.Random(10_000 + seed)
    cap = rng.choice([23, 37, 50])
    ref = LRUCache(cap)
    st = IntervalLRUState(cap)
    for _ in range(150):
        obj = rng.randrange(2)
        lo = obj * 1000 + rng.randrange(0, 40)
        hi = lo + rng.randrange(0, 10)
        size = rng.choice([1, 2, 5])
        nh_i, miss_runs = st.lookup_touch(obj, lo, hi, size)
        all_miss = [k for a, b in miss_runs for k in range(a, b)]
        peer = set(k for k in all_miss if rng.random() < 0.4)
        # reference: lookup+touch, then peer inserts, then origin inserts
        missing, nh_r = [], 0
        for k in range(lo, hi):
            if ref.lookup(k, size):
                nh_r += 1
            else:
                missing.append(k)
        for k in (k for k in missing if k in peer):
            ref.insert(k, size)
        for k in (k for k in missing if k not in peer):
            ref.insert(k, size)
        assert nh_r == nh_i
        st.insert_runs(obj, _runs_from(peer), size)
        st.insert_runs(obj, _runs_from(set(all_miss) - peer), size)
        st.check_invariants()
        assert keys_of(st) == sorted(ref._od.keys())
        assert st.evictions == ref.stats.evictions


# ---------------------------------------------------------------------------
# engine-side interval utilities
# ---------------------------------------------------------------------------


def test_coalesce_peer_fetches_groups_ranges():
    req = np.array([3, 3, 3, 3, 7], np.int64)
    keys = np.array([10, 11, 12, 20, 10], np.int64)
    src = np.array([2, 2, 4, 2, 2], np.int64)
    got = coalesce_peer_ranges(req, np.ones(len(req), np.int64), src, keys,
                               keys + 1)
    assert got == [
        PeerFetchRange(3, 1, 2, 10, 12),
        PeerFetchRange(3, 1, 4, 12, 13),
        PeerFetchRange(3, 1, 2, 20, 21),
        PeerFetchRange(7, 1, 2, 10, 11),
    ]


def test_select_peer_sources_rules():
    # bandwidth into the requesting DTN: origin=5; peers 2 and 3 tie at 8,
    # peer 4 has 9 but only holds chunk 2; peer 5 is below the origin link
    bw = np.array([5.0, 0.0, 8.0, 8.0, 9.0, 4.0])
    holders = np.zeros((6, 4), bool)
    holders[2, 0] = holders[3, 0] = True      # tie -> lowest DTN id wins
    holders[4, 1] = True                      # best bw
    holders[5, 2] = True                      # below origin -> rejected
    src, acc = select_peer_sources(bw, holders)
    assert acc.tolist() == [True, True, False, False]
    assert src[0] == 2 and src[1] == 4


# ---------------------------------------------------------------------------
# flat array-backed state (PR 7): snapshot freshness, eviction-plan clamp,
# and randomized flat-vs-list differential coverage
# ---------------------------------------------------------------------------


_STATES = [IntervalLRUState, FlatIntervalState]


@pytest.mark.parametrize("cls", _STATES)
def test_snapshot_fresh_after_eviction(cls):
    """Evict-then-snapshot regression: ``coverage_arrays`` memoizes the
    per-object size-run conversion (the list state's ``_zmemo``), and every
    size-map mutation — insert, eviction, block commit — must invalidate
    it.  A stale memo here would silently corrupt every later fused block's
    start-of-block presence snapshot."""
    st = cls(4)
    st.serve(0, 0, 4, 1)                   # fill [0, 4) exactly
    ss, ee = st.coverage_arrays()             # populates the memo
    assert (ss.tolist(), ee.tolist()) == ([0], [4])
    # inserting [10, 12) evicts the two oldest chunks of the first record
    st.serve(0, 10, 12, 1)
    ss, ee = st.coverage_arrays()
    assert (ss.tolist(), ee.tolist()) == ([2, 10], [4, 12])
    assert st.evictions == 2 and st.used == 4
    # a fused block commit must invalidate too (the commit path bypasses
    # insert_runs); evict room first so the commit is in-contract
    st._evict_until(1)
    st.commit_block([(0, 20, 21, 1)], [(0, 20, 21)])
    ss, ee = st.coverage_arrays()
    assert (ss.tolist(), ee.tolist()) == ([3, 10, 20], [4, 12, 21])
    st.check_invariants()


@pytest.mark.parametrize("cls", _STATES)
def test_plan_evict_clean_clamps_mid_segment(cls):
    """A presence run whose byte tally crosses ``max_need`` mid-segment is
    consumed whole by the scan; the result must come back clamped at
    ``max_need`` — never the overshot run total.  The fused-replay call
    site only ever compares the result against the shortfall, so the clamp
    is contract-neutral there (see ``plan_evict_clean``'s docstring)."""
    st = cls(1000)
    st.serve(0, 0, 10, 4)                  # one 10-chunk size-4 record
    # need lands mid-run (10 bytes = 2.5 chunks into a 40-byte run)
    assert st.plan_evict_clean(10, [], []) == 10
    # a blocked run inside the segment truncates the scan at its start
    assert st.plan_evict_clean(1000, [4], [6]) == 16
    # unblocked and unclamped: the whole record's bytes
    assert st.plan_evict_clean(1000, [], []) == 40


def _state_digest(st):
    return dict(hits=st.hits, misses=st.misses, hit_bytes=st.hit_bytes,
                miss_bytes=st.miss_bytes, evictions=st.evictions,
                inserted_bytes=st.inserted_bytes, used=st.used,
                n_live=st.n_live, iv=st.intervals(),
                obj_hi=dict(st.obj_hi))


@pytest.mark.parametrize("seed", range(24))
def test_flat_matches_list_randomized(seed):
    """Differential fuzz of FlatIntervalState against IntervalLRUState
    across the full behavioral API: serve, lookup_touch, coverage queries,
    fused block commits, eviction plans — digests (counters, intervals)
    must agree at every checkpoint."""
    span = 1 << 20
    rng = random.Random((20260808, "flat-vs-list", seed).__repr__())
    cap = rng.choice([200, 1000, 5000])
    a = IntervalLRUState(cap)
    b = FlatIntervalState(cap)
    sizes: dict = {}
    for step in range(130):
        op = rng.random()
        obj = rng.randrange(4)
        size = sizes.setdefault(obj, rng.choice([1, 3, 7, 16]))
        lo = obj * span + rng.randrange(300)
        hi = lo + rng.randrange(1, 60)
        if op < 0.55:
            assert a.serve(obj, lo, hi, size) == \
                b.serve(obj, lo, hi, size)
        elif op < 0.72:
            ra = a.lookup_touch(obj, lo, hi, size)
            rb = b.lookup_touch(obj, lo, hi, size)
            assert ra[0] == rb[0] and list(ra[1]) == list(rb[1])
        elif op < 0.82:
            assert a.coverage_runs(obj, lo, hi) == b.coverage_runs(obj, lo,
                                                                   hi)
        elif op < 0.92:
            # fused-style block commit: disjoint absent runs, in-contract
            # (the engine evicts ahead of commits)
            held = set(k for s, e in a.intervals() for k in range(s, e))
            recs_z, recs_r = [], []
            pos = obj * span + rng.randrange(400)
            for _ in range(rng.randrange(1, 4)):
                w = rng.randrange(1, 20)
                run = sorted(k for k in range(pos, pos + w)
                             if k not in held)
                pos += w + rng.randrange(0, 10)
                i = 0
                while i < len(run):
                    j = i
                    while j + 1 < len(run) and run[j + 1] == run[j] + 1:
                        j += 1
                    recs_z.append((obj, run[i], run[j] + 1, size))
                    recs_r.append((obj, run[i], run[j] + 1))
                    held.update(range(run[i], run[j] + 1))
                    i = j + 1
            tot = sum((e0 - s0) * sz for _, s0, e0, sz in recs_z)
            if recs_z and a.used + tot <= cap:
                a.commit_block(recs_z, recs_r)
                b.commit_block(recs_z, recs_r)
        else:
            mn = rng.randrange(1, cap)
            bl = sorted(rng.sample(range(obj * span, obj * span + 400), 4))
            pa = a.plan_evict_clean(mn, [bl[0], bl[2]], [bl[1], bl[3]])
            pb = b.plan_evict_clean(mn, [bl[0], bl[2]], [bl[1], bl[3]])
            assert pa == pb
        if step % 13 == 0:
            a.check_invariants()
            b.check_invariants()
            assert _state_digest(a) == _state_digest(b)
    assert _state_digest(a) == _state_digest(b)


@pytest.mark.parametrize("cls", _STATES)
def test_plan_invalidated_by_mid_block_commit(cls):
    """A commit whose recency record lands inside a speculative plan's
    victim set must drop the cached plan: the re-stamped run is now MRU,
    so consuming the stale plan would evict the wrong victims.  Proven by
    transparency — the planned state must stay digest-identical to a twin
    that never planned (planning is a pure, cached scan)."""
    planned = cls(100)
    twin = cls(100)
    for st in (planned, twin):
        st.serve(0, 0, 30, 1)          # record A — the oldest victim
        st.serve(0, 100, 130, 1)       # record B
        st.serve(0, 200, 230, 1)       # record C; used = 90 of 100
    clean = planned.plan_evict_clean(40, [], [])
    assert clean == 40 and planned._plan is not None
    # mid-block commit: insert D (fits the remaining room) and re-stamp
    # [5, 25) — strictly inside planned victim A — to recency t=3
    recs_z = [(0, 300, 310, 1)]
    recs_r = [(0, 5, 25), (0, 300, 310)]
    for st in (planned, twin):
        st.commit_block(recs_z, recs_r)
    assert planned._plan is None          # the guard must have fired
    # eviction pressure: 40 inserted bytes evict the A remnants (10) and
    # B (30) in true LRU order; a stale plan would have taken all of A
    for st in (planned, twin):
        st.serve(0, 400, 440, 1)
    assert planned.coverage_runs(0, 0, 30) == [(5, 25)]
    assert _state_digest(planned) == _state_digest(twin)
    planned.check_invariants()
    twin.check_invariants()


@pytest.mark.parametrize("cls", _STATES)
def test_plan_invalidated_by_inblock_victim_restab(cls):
    """Phased-replay hazard (ISSUE 10): a boundary plan may list records
    that were committed by EARLIER PHASES of the same block — in-block
    victims, entered via ``commit_block`` rather than ``serve``.  A later
    phase's commit that re-stamps such a victim must invalidate the plan
    exactly like the pre-block record-stab rule above; the stab guard must
    not depend on how the victim record was created.  Transparency twin
    proves the whole sequence."""
    planned = cls(100)
    twin = cls(100)
    # phase-1-style commit: A, B, C enter through the fused commit path
    # (in-block records), not through serve
    recs_z1 = [(0, 0, 30, 1), (0, 100, 130, 1), (0, 200, 230, 1)]
    recs_r1 = [(0, 0, 30), (0, 100, 130), (0, 200, 230)]
    for st in (planned, twin):
        st.commit_block(recs_z1, recs_r1)
    assert planned.used == 90
    # phase boundary: plan 40 clean bytes — victim prefix is in-block A
    # plus the head of in-block B
    clean = planned.plan_evict_clean(40, [], [])
    assert clean == 40 and planned._plan is not None
    # phase-2 commit re-touches [5, 25) inside in-block victim A
    recs_z2 = [(0, 300, 310, 1)]
    recs_r2 = [(0, 5, 25), (0, 300, 310)]
    for st in (planned, twin):
        st.commit_block(recs_z2, recs_r2)
    assert planned._plan is None          # stab guard fired on in-block A
    # pressure: the A remnants (10) + B (30) must go in true LRU order
    for st in (planned, twin):
        st.serve(0, 400, 440, 1)
    assert planned.coverage_runs(0, 0, 30) == [(5, 25)]
    assert _state_digest(planned) == _state_digest(twin)
    planned.check_invariants()
    twin.check_invariants()


def test_flat_plan_fgen_stale_early_return_is_safe():
    """``FlatIntervalState.get_evict_plan`` returns a cached plan that
    already covers the queried need WITHOUT checking ``fgen`` (see the
    comment at that early return): ``clean_before`` reads only the victim
    key runs against the *current* size map, and ``_evict_until``
    re-validates ``fgen`` before consuming.  Phased replay makes this path
    hot — phase commits compact the FIFO (fgen bump) between boundary
    plans — so pin the safety argument: plan, force a real compaction via
    recency churn on non-victims, re-query through the stale-fgen early
    return, then evict, all digest-identical to a plan-free twin."""
    planned = FlatIntervalState(10_000)
    twin = FlatIntervalState(10_000)
    for st in (planned, twin):
        for k in range(40):
            st.serve(0, 10 * k, 10 * k + 10, 1)   # 400 chunks, no evict
    assert planned.plan_evict_clean(50, [], []) == 50
    p = planned._plan
    assert p is not None
    g0 = planned._fgen
    # churn recency on records past the plan's key span (kmax) only, so
    # the stab guard never fires — until the FIFO array fills and a
    # compaction renumbers positions
    first_safe = -(-int(p.kmax) // 10)    # record index just past kmax
    assert first_safe < 40
    step = 0
    while planned._fgen == g0:
        assert step < 5000, "compaction never triggered"
        idx = first_safe + (step % (40 - first_safe))
        for st in (planned, twin):
            st.lookup_touch(0, 10 * idx, 10 * idx + 10, 1)
        step += 1
    assert planned._plan is p             # plan survived with stale fgen
    # covered-need query takes the fgen-less early return; its clean-byte
    # answer must agree with the twin's fresh scan
    assert planned.plan_evict_clean(40, [], []) == \
        twin.plan_evict_clean(40, [], [])
    # real pressure: _evict_until sees p.fgen != self._fgen, drops the
    # stale plan and walks fresh — digests must stay identical
    for st in (planned, twin):
        st.serve(0, 1 << 20, (1 << 20) + 9_700, 1)
    assert _state_digest(planned) == _state_digest(twin)
    planned.check_invariants()
    twin.check_invariants()


@pytest.mark.parametrize("cls", _STATES)
@pytest.mark.parametrize("seed", range(8))
def test_plan_is_semantically_inert_randomized(cls, seed):
    """Seeded transparency fuzz: interleave speculative plans (on one state
    only) with serves, lookups and fused commits whose recency records may
    land on present runs — including planned victims.  The planning state
    must remain digest-identical to a plan-free twin at every checkpoint,
    whatever mix of plan reuse, extension and invalidation occurs."""
    span = 1 << 20
    rng = random.Random((20260808, "plan-inert", seed,
                         cls.__name__).__repr__())
    cap = rng.choice([150, 600])
    planned = cls(cap)
    twin = cls(cap)
    sizes: dict = {}
    for step in range(120):
        op = rng.random()
        obj = rng.randrange(3)
        size = sizes.setdefault(obj, rng.choice([1, 2, 5]))
        lo = obj * span + rng.randrange(250)
        hi = lo + rng.randrange(1, 40)
        if op < 0.45:
            assert planned.serve(obj, lo, hi, size) == \
                twin.serve(obj, lo, hi, size)
        elif op < 0.65:
            # speculative plan on one state only (pure scan, cached)
            bl = sorted(rng.sample(range(obj * span, obj * span + 300), 2))
            planned.plan_evict_clean(rng.randrange(1, cap), [bl[0]],
                                     [bl[1]])
        elif op < 0.85:
            # fused commit: disjoint absent runs + one recency record over
            # a random present run (the mid-plan re-stamp the guard is for)
            held = set(k for s, e in planned.intervals()
                       for k in range(s, e))
            recs_z, recs_r = [], []
            pos = obj * span + rng.randrange(300)
            for _ in range(rng.randrange(1, 3)):
                w = rng.randrange(1, 15)
                run = sorted(k for k in range(pos, pos + w)
                             if k not in held)
                pos += w + rng.randrange(0, 8)
                i = 0
                while i < len(run):
                    j = i
                    while j + 1 < len(run) and run[j + 1] == run[j] + 1:
                        j += 1
                    recs_z.append((obj, run[i], run[j] + 1, size))
                    recs_r.append((obj, run[i], run[j] + 1))
                    held.update(range(run[i], run[j] + 1))
                    i = j + 1
            iv = planned.intervals()
            if iv and rng.random() < 0.7:
                s, e = iv[rng.randrange(len(iv))]
                s2 = rng.randrange(s, e)
                e2 = rng.randrange(s2 + 1, e + 1)
                recs_r.append((s2 // span, s2, e2))
            tot = sum((e0 - s0) * sz for _, s0, e0, sz in recs_z)
            if recs_r and planned.used + tot <= cap:
                planned.commit_block(recs_z, recs_r)
                twin.commit_block(recs_z, recs_r)
        else:
            ra = planned.lookup_touch(obj, lo, hi, size)
            rb = twin.lookup_touch(obj, lo, hi, size)
            assert ra[0] == rb[0] and list(ra[1]) == list(rb[1])
        if step % 11 == 0:
            planned.check_invariants()
            twin.check_invariants()
            assert _state_digest(planned) == _state_digest(twin)
    assert _state_digest(planned) == _state_digest(twin)
