"""Applying prefetch ops on the vector engine (``_apply_prefetch``).

The engine finds an op's finalized chunks as one contiguous run of the
DTN's presence row and returns before building any array when every one of
them is cached (or none is finalized), counting such ops in
``prefetch_noop``.  Each case applies a sequence of ops to two identically
prepared simulators, one through the engine and one through the array path
the engine used before (kept below as the oracle), and requires the same
state after every op: presence, prefetch marks, every cache's bytes,
stamps and FIFO (or LFU heap), the origin queue, the issued-chunk count and
the no-op count.
"""
import math

import numpy as np
import pytest

from repro.core import SimConfig, StreamingRequestSource, make_trace
from repro.core import run_strategy, telemetry
from repro.core.engine import _PREFETCH_WALK_MAX, VectorVDCSimulator
from repro.core.hpm import PrefetchOp
from repro.core.trace import (GAGE_PROFILE, ObjectGrid, Request,
                              requests_to_arrays)

H = 3600.0
N = _PREFETCH_WALK_MAX
GRID = ObjectGrid(2, 2)
RATE = 8e3
USER_DTN = {0: 1, 1: 2}
NO_DTN_USER = 5
# chunks [10, 20) of object 1 are in DTN 1's cache before the ops run
PRESENT = (1, 1, 10, 20)


def array_path(sim, op, now):
    """The engine's array path before the contiguous-range walk: keys by
    ``np.arange``, a finalized mask, then a gather of the presence row."""
    if not sim.use_cache:
        return
    dtn = sim._user_dtn.get(op.user_id)
    if dtn is None:
        return
    cs = sim.cfg.chunk_seconds
    e = min(op.tr_end, now)
    if e <= op.tr_start:
        return
    c_first = int(math.floor(op.tr_start / cs))
    c_last = int(math.ceil(e / cs))
    if c_first + sim._off < 0 or c_last + sim._off > sim._span:
        sim._grow(c_first, c_last)
    base = op.obj * sim._span + sim._off
    keys = np.arange(base + c_first, base + c_last, dtype=np.int64)
    cvec = np.arange(c_first, c_last, dtype=np.int64)
    keys = keys[(cvec + 1) * cs <= now]
    if not len(keys):
        sim._pref_noop += 1
        return
    cache = sim.caches[dtn]
    new_keys = keys[~sim._present2d[dtn, keys]]
    if not len(new_keys):
        sim._pref_noop += 1
        return
    nbytes = sim._chunk_bytes * len(new_keys)
    sim.origin.submit(now, sim._origin_dur(nbytes, dtn), with_overhead=False)
    cache.insert_batch(new_keys, sim._chunk_bytes)
    sim._mark_prefetched(dtn, new_keys)


def make_sim(policy, cs, capacity_chunks):
    cfg = SimConfig(cache_policy=policy, chunk_seconds=cs,
                    cache_bytes=capacity_chunks * int(RATE * cs),
                    stream_rate_bytes_per_s=RATE, enable_placement=False)
    sim = VectorVDCSimulator(GRID, None, cfg)
    # the address space covers chunks [0, 40) of every object
    reqs = [Request(100 * cs, u, obj, 0.0, 40 * cs, 1000, 0)
            for u, obj in ((0, 0), (1, 3))]
    sim._prep_window(requests_to_arrays(reqs))
    sim._user_dtn.update(USER_DTN)
    dtn, obj, c0, c1 = PRESENT
    base = obj * sim._span + sim._off
    keys = np.arange(base + c0, base + c1, dtype=np.int64)
    sim.caches[dtn].insert_batch(keys, sim._chunk_bytes)
    sim._mark_prefetched(dtn, keys)
    return sim


def cache_state(c):
    out = {"used": c.used, "n_live": c.n_live, "evictions": c.evictions,
           "inserted_bytes": c.inserted_bytes, "size": c.size.copy()}
    if c.policy == "lru":
        out.update(stamp=c.stamp.copy(), clock=c._clock,
                   fifo_stamps=c._fs[c._head:c._tail].copy(),
                   fifo_keys=c._fk[c._head:c._tail].copy())
    else:
        out.update(freq=c.freq.copy(), heap=sorted(c._heap), seq=c._seq)
    return out


def state(sim):
    out = {"off": sim._off, "span": sim._span,
           "present": sim._present2d.copy(), "pref": sim._pref2d.copy(),
           "free_at": list(sim.origin.free_at),
           "issued": sim._pref_issued, "noop": sim._pref_noop}
    for d, c in sim.caches.items():
        out.update({f"dtn{d}.{k}": v for k, v in cache_state(c).items()})
    return out


def assert_same(got, want):
    assert got.keys() == want.keys()
    for k in want:
        if isinstance(want[k], np.ndarray):
            np.testing.assert_array_equal(got[k], want[k], err_msg=k)
        else:
            assert got[k] == want[k], k


def op(user, obj, c0, c1, cs=H):
    return PrefetchOp(0.0, user, obj, c0 * cs, c1 * cs, "history")


# (policy, chunk seconds, capacity in chunks, [(op, now)], no-ops expected)
CASES = {
    "all_present": ("lru", H, 64, [(op(0, 1, 10, 20), 100 * H)], 1),
    "some_present": ("lru", H, 64, [(op(0, 1, 5, 25), 100 * H),
                                    (op(0, 1, 5, 25), 100 * H)], 1),
    "none_finalized": ("lru", H, 64, [(op(0, 0, 30, 40), 30.5 * H),
                                      (op(0, 0, 30, 30.2), 31 * H)], 1),
    "live_tail": ("lru", H, 64, [(op(0, 0, 20, 40), 35.5 * H),
                                 (op(0, 0, 20, 40), 35.9 * H),
                                 (op(0, 0, 20, 40), 36.0 * H)], 1),
    "now_on_chunk_edge": ("lru", H, 64, [(op(0, 0, 20, 40), 35 * H),
                                         (op(0, 0, 20, 35), 35 * H)], 1),
    # the float predicate, where now / cs rounds the other way: 3 * 0.1 and
    # 17 * 0.1 lie above 0.3 and 1.7, 43 * 0.1 is 4.3 but 4.3 / 0.1 < 43
    "float_chunk_edge": ("lru", 0.1, 64,
                         [(PrefetchOp(0.0, 0, 0, 0.0, 0.3, "history"), 0.3),
                          (PrefetchOp(0.0, 0, 0, 0.0, 0.3, "history"),
                           0.30000000000000004),
                          (PrefetchOp(0.0, 0, 0, 1.0, 2.0, "history"), 1.7),
                          (PrefetchOp(0.0, 0, 0, 4.0, 4.5, "history"), 4.3)],
                         0),
    "empty_after_clip": ("lru", H, 64, [(op(0, 0, 30, 40), 25 * H),
                                        (op(0, 0, 30, 30), 50 * H)], 0),
    "user_without_dtn": ("lru", H, 64, [(op(NO_DTN_USER, 1, 0, 30), 100 * H)],
                         0),
    "walk_under_threshold": ("lru", H, 64,
                             [(op(0, 1, 10, 10 + N - 1), 100 * H),
                              (op(0, 1, 10, 10 + N - 1), 100 * H),
                              (op(0, 1, 5, 4 + N), 100 * H)], 1),
    "slice_over_threshold": ("lru", H, 64,
                             [(op(0, 1, 9, 10 + N), 100 * H),
                              (op(0, 1, 9, 10 + N), 100 * H),
                              (op(0, 1, 9, 11 + N), 100 * H)], 1),
    "last_chunk_missing": ("lru", H, 64, [(op(0, 1, 10, 21), 100 * H)], 0),
    "grow": ("lru", H, 128, [(op(0, 2, 80, 95), 80.5 * H),
                            (op(0, 2, -20, -5), 100 * H),
                            (op(0, 2, 30, 90), 100 * H),
                            (op(0, 2, -20, -5), 100 * H),
                            (op(0, 1, 10, 20), 100 * H)], 3),
    "evicting": ("lru", H, 12, [(op(0, 0, 0, 8), 100 * H),
                                (op(0, 1, 12, 22), 100 * H),
                                (op(1, 3, 0, 30), 100 * H)], 0),
    "lfu": ("lfu", H, 14, [(op(0, 1, 10, 20), 100 * H),
                           (op(0, 1, 5, 25), 100 * H),
                           (op(0, 1, 20, 25), 100 * H),
                           (op(1, 0, 0, 30), 100 * H)], 2),
}


@pytest.mark.parametrize("case", list(CASES))
def test_apply_prefetch_matches_the_array_path(case):
    policy, cs, capacity, ops, n_noop = CASES[case]
    sim = make_sim(policy, cs, capacity)
    oracle = make_sim(policy, cs, capacity)
    assert_same(state(sim), state(oracle))
    for o, now in ops:
        sim._apply_prefetch(o, now)
        array_path(oracle, o, now)
        assert_same(state(sim), state(oracle))
    assert sim._pref_noop == n_noop


def test_noop_counter_in_loop_and_drain_meta(monkeypatch):
    """A short streamed hpm replay, recorded: every loop and drain span
    carries ``prefetch_noop``, never above its ``prefetch_events``, and
    the spans add up to the always-on counter."""
    tr = make_trace("gage", seed=3, scale=0.1)
    cut = int(len(tr) * 0.3)
    train, test = tr[:cut], tr[cut:]
    cfg = SimConfig(cache_bytes=1 << 30,
                    stream_rate_bytes_per_s=GAGE_PROFILE.bytes_per_second_stream
                    ).calibrate_origin(test)
    monkeypatch.setattr(telemetry, "enabled", lambda: True)
    telemetry.reset()
    source = StreamingRequestSource.from_requests(test,
                                                  window=len(test) // 4 + 1)
    run_strategy("hpm", source, GAGE_PROFILE.grid, cfg, train)
    recs = telemetry.records()
    loops = [r for r in recs if r.name == "vdc.engine.loop"]
    drains = [r for r in recs if r.name == "vdc.engine.drain"]
    assert len(loops) > 1 and drains
    for r in loops + drains:
        assert 0 <= r.meta["prefetch_noop"] <= r.meta["prefetch_events"]
    total = sum(r.meta["prefetch_noop"] for r in loops + drains)
    assert total == telemetry.counters()["prefetch_noop"] > 0
    telemetry.reset()
