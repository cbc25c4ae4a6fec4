"""Streaming == materialized replay equivalence (the paper-scale contract).

The streaming path exists so the paper's full traces (17.9M OOI / 77.8M GAGE
requests, §V-A1) can be replayed in bounded memory.  Its whole correctness
story is one contract: feeding :class:`StreamingRequestSource` windows to an
engine must yield *exactly* the integer counters of the fully materialized
run — same cache hits/misses/evictions, same byte splits, same origin-queue
submits — with float aggregates equal to summation-order rounding.  This
module pins that contract across all three engines x all five strategies on
the seeded OOI/GAGE traces (plus the interval engine's execution knobs), the
synthesizer's determinism/prefix guarantees, and the bounded-memory property
the tentpole is for (slow-marked).  A materialized list is itself replayed as
the single window of such a source; that contract and the interval engine's
route choice are pinned here too.
"""
import dataclasses
import itertools
import resource

import numpy as np
import pytest

from repro.core import SimConfig, make_trace, run_strategy
from repro.core.cache import IntervalLRUState
from repro.core.delivery import make_prefetcher
from repro.core.engine import IntervalVDCSimulator
from repro.core.interval_store import FlatIntervalState
from repro.core.simulator import OutcomeAggregate, SimResult
from repro.core.trace import (GAGE_PROFILE, OOI_PROFILE, ObjectGrid,
                              Request, RequestList, StreamingRequestSource,
                              StreamingTraceSynthesizer)

PROFILES = {"ooi": OOI_PROFILE, "gage": GAGE_PROFILE}

ENGINES = ("reference", "vector", "interval")
STRATEGIES = ("no_cache", "cache_only", "md1", "md2", "hpm")

#: a prime window width so window edges land at arbitrary offsets inside
#: blocks, event bursts and HPM user histories
WINDOW = 997

_MAT_CACHE: dict = {}


@pytest.fixture(scope="module")
def splits():
    out = {}
    for name in ("ooi", "gage"):
        tr = make_trace(name, seed=7, scale=0.035)
        cut = int(len(tr) * 0.3)
        out[name] = (tr[:cut], tr[cut:])
    return out


def _cfg(trace, test, **kw):
    kw.setdefault("cache_bytes", 1 << 30)
    cfg = SimConfig(
        stream_rate_bytes_per_s=PROFILES[trace].bytes_per_second_stream, **kw)
    return cfg.calibrate_origin(test)


def _int_counters(res):
    """Every integer the engines promise to agree on, plus per-DTN stats."""
    agg = res.outcome_totals()
    return {
        "origin_requests": res.origin_requests,
        "total_requests": res.total_requests,
        "prefetch_issued": res.prefetch_issued_chunks,
        "prefetch_used": res.prefetch_used_chunks,
        "stream_pushes": res.stream_pushes,
        "cache_stats": {
            d: (s.hits, s.misses, s.hit_bytes, s.miss_bytes, s.evictions,
                s.inserted_bytes)
            for d, s in res.cache_stats.items()
        },
        "n": agg.n,
        "n_bytes_pos": agg.n_bytes_pos,
        "bytes": agg.bytes,
        "local_bytes": agg.local_bytes,
        "prefetched_bytes": agg.prefetched_bytes,
        "peer_bytes": agg.peer_bytes,
        "origin_bytes": agg.origin_bytes,
    }


def _assert_float_close(mat, stream):
    am, as_ = mat.outcome_totals(), stream.outcome_totals()
    for f in ("latency_sum", "transfer_sum", "peer_time_sum",
              "throughput_sum"):
        x, y = getattr(am, f), getattr(as_, f)
        assert abs(x - y) <= 1e-9 * max(1.0, abs(x)), (f, x, y)


def _mat_run(trace, splits, strategy, engine, **cfg_kw):
    key = (trace, strategy, engine, tuple(sorted(cfg_kw.items())))
    if key not in _MAT_CACHE:
        train, test = splits[trace]
        _MAT_CACHE[key] = run_strategy(
            strategy, test, PROFILES[trace].grid,
            _cfg(trace, test, **cfg_kw), train, engine=engine)
    return _MAT_CACHE[key]


# ---------------------------------------------------------------------------
# engine x strategy matrix
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("trace", ("ooi", "gage"))
def test_streaming_equals_materialized(trace, strategy, engine, splits):
    mat = _mat_run(trace, splits, strategy, engine)
    train, test = splits[trace]
    src = StreamingRequestSource.from_requests(test, window=WINDOW)
    stream = run_strategy(strategy, src, PROFILES[trace].grid,
                          _cfg(trace, test), train, engine=engine)
    assert _int_counters(mat) == _int_counters(stream)
    _assert_float_close(mat, stream)


@pytest.mark.parametrize("cfg_kw,route", [
    ({"interval_flat_state": True}, None),
    ({"interval_flat_state": False}, None),
    ({"chunk_seconds": 60.0}, "sweep"),     # fine chunking, sweep pinned
    # eviction-pressure legs: a cache far below the working set keeps the
    # fused path planning/truncating across window boundaries, so the
    # speculative eviction plan's reuse-and-invalidate lifecycle is pinned
    # under the streaming==materialized contract for both state layouts
    ({"cache_bytes": 1 << 22}, None),
    ({"cache_bytes": 1 << 22, "interval_flat_state": False}, None),
], ids=["flat_on", "flat_off", "sweep", "thrash_flat", "thrash_list"])
def test_streaming_interval_knobs(cfg_kw, route, splits, interval_route):
    trace, strategy = "ooi", "cache_only"
    train, test = splits[trace]
    src = StreamingRequestSource.from_requests(test, window=WINDOW)
    with interval_route(route):
        mat = run_strategy(strategy, test, PROFILES[trace].grid,
                           _cfg(trace, test, **cfg_kw), train,
                           engine="interval")
        stream = run_strategy(strategy, src, PROFILES[trace].grid,
                              _cfg(trace, test, **cfg_kw), train,
                              engine="interval")
    assert _int_counters(mat) == _int_counters(stream)
    _assert_float_close(mat, stream)


def test_window_width_one_and_whole_trace(splits):
    """Degenerate windowings: width 1 (a window per request) and a single
    window covering the whole trace must both match."""
    trace, strategy = "gage", "md1"
    mat = _mat_run(trace, splits, strategy, "vector")
    train, test = splits[trace]
    for w in (1, len(test)):
        src = StreamingRequestSource.from_requests(test, window=w)
        stream = run_strategy(strategy, src, PROFILES[trace].grid,
                              _cfg(trace, test), train, engine="vector")
        assert _int_counters(mat) == _int_counters(stream), w


#: outcome fields in the order the engines fold their columns
#: (``OutcomeAggregate.add_columns``)
_FOLDED_FIELDS = ("bytes", "latency", "transfer_time", "local_bytes",
                  "prefetched_bytes", "peer_bytes", "origin_bytes",
                  "peer_time")


@pytest.mark.parametrize("engine,strategy", [
    ("vector", "cache_only"), ("vector", "hpm"), ("vector", "md2"),
    ("interval", "cache_only")])
def test_list_equals_one_window_source(engine, strategy, splits,
                                       monkeypatch):
    """A list is replayed as the single window of a source: every
    ``SimResult`` field of the list run equals the one-window source run's,
    and its per-request ``outcomes`` are exactly the columns that run
    folds."""
    trace = "ooi"
    lst = _mat_run(trace, splits, strategy, engine)
    folded = []
    add_columns = OutcomeAggregate.add_columns

    def capture(agg, *cols):
        folded.append([np.array(c, copy=True) for c in cols])
        add_columns(agg, *cols)

    monkeypatch.setattr(OutcomeAggregate, "add_columns", capture)
    train, test = splits[trace]
    src = StreamingRequestSource.from_requests(test, window=len(test))
    one = run_strategy(strategy, src, PROFILES[trace].grid,
                       _cfg(trace, test), train, engine=engine)
    assert len(folded) == 1
    assert lst.aggregate is None and list(one.outcomes) == []
    for f in dataclasses.fields(SimResult):
        if f.name not in ("outcomes", "aggregate"):
            assert getattr(lst, f.name) == getattr(one, f.name), f.name
    assert [o.ts for o in lst.outcomes] == [r.ts for r in test]
    assert [o.user_id for o in lst.outcomes] == [r.user_id for r in test]
    for name, col in zip(_FOLDED_FIELDS, folded[0]):
        np.testing.assert_array_equal(
            np.array([getattr(o, name) for o in lst.outcomes]), col,
            err_msg=name)


@pytest.mark.parametrize("chunking", ["coarse", "fine"])
@pytest.mark.parametrize("feed", ["list", "source"])
def test_interval_route_choice(feed, chunking):
    """The interval engine picks its static route from the first window's
    mean chunk positions per request — for a list, the whole trace's: the
    fused block replay on flat interval states at hourly chunks (8 a
    request here), the interval sweep on list-backed states at 60 s chunks
    (480 a request)."""
    grid = ObjectGrid(2, 2)
    trace = RequestList(
        Request(3600.0 * (20 + i), 1 + i % 3, i % 2, 3600.0 * i,
                3600.0 * (i + 8), 8 << 20, i % 3) for i in range(30))
    cfg = SimConfig(chunk_seconds=3600.0 if chunking == "coarse" else 60.0,
                    enable_placement=False).calibrate_origin(trace)
    sim = IntervalVDCSimulator(grid, make_prefetcher("cache_only", grid),
                               cfg)
    sim.run(trace if feed == "list"
            else StreamingRequestSource.from_requests(trace, window=7))
    want = FlatIntervalState if chunking == "coarse" else IntervalLRUState
    assert {type(c) for c in sim.caches.values()} == {want}


# ---------------------------------------------------------------------------
# synthesizer guarantees
# ---------------------------------------------------------------------------


def _small_synth(seed=3, n=5000):
    return StreamingTraceSynthesizer(OOI_PROFILE, seed=seed, n_requests=n,
                                     n_users=300)


def test_synthesizer_deterministic():
    a = list(_small_synth().iter_requests())
    b = list(_small_synth().iter_requests())
    assert a == b
    assert a != list(_small_synth(seed=4).iter_requests())


def test_synthesizer_prefix_equals_materialize():
    s = _small_synth()
    prefix = list(itertools.islice(s.iter_requests(), 1000))
    assert prefix == list(s.materialize(1000))
    # timestamp order and declared bounds hold
    ts = [r.ts for r in prefix]
    assert ts == sorted(ts)
    lo, hi = s.tr_bounds
    assert all(lo <= r.tr_start <= r.tr_end <= hi for r in prefix)


def test_source_windows_concat_equals_materialize():
    s = _small_synth()
    mat = s.materialize()
    assert len(mat) == 5000
    src = s.source(window=613)
    cat = [r for w in src.windows() for r in w]
    assert cat == list(mat)
    # sources are restartable: a second pass yields the same stream
    assert [r for w in src.windows() for r in w] == cat


def test_source_facade_protocol():
    s = _small_synth(n=100)
    src = s.source(window=32)
    assert len(src) == 100
    assert bool(src)                      # truthy even when length unknown
    assert len(list(src)) == 100          # plain iteration works
    unsized = StreamingRequestSource(s.iter_requests, window=32)
    with pytest.raises(TypeError):
        len(unsized)
    assert bool(unsized)
    with pytest.raises(ValueError):
        StreamingRequestSource(s.iter_requests, window=0)


def test_from_requests_bounds():
    reqs = RequestList(_small_synth(n=50).materialize())
    src = StreamingRequestSource.from_requests(reqs, window=7)
    lo, hi = src.tr_bounds
    assert lo == min(r.tr_start for r in reqs)
    assert hi == max(r.tr_end for r in reqs)
    assert len(src) == 50


# ---------------------------------------------------------------------------
# bounded memory (the regression guard for the whole tentpole)
# ---------------------------------------------------------------------------


def _peak_rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


@pytest.mark.slow
def test_streaming_memory_flat_as_trace_doubles():
    """Peak RSS must stay flat (within a fixed budget) when the streamed
    trace doubles from ~1M to ~2M requests.  ``ru_maxrss`` is a process
    high-water mark, so the runs go small-then-large and the assertion
    bounds the *increment*: an O(n) leak would roughly double the peak,
    a windowed replay only adds jitter."""
    # near-zero realtime share so request count scales with duration
    profile = dataclasses.replace(OOI_PROFILE,
                                  type_volume_mix=(0.35, 0.001, 0.649))
    grid = profile.grid

    def run(n_requests):
        synth = StreamingTraceSynthesizer(profile, seed=5,
                                          n_requests=n_requests,
                                          n_users=4000)
        # a capacity that holds thousands of chunks: tiny caches degenerate
        # block replay to per-request eviction churn (correct but slow),
        # which would turn this memory guard into a time sink
        cfg = SimConfig(
            stream_rate_bytes_per_s=profile.bytes_per_second_stream,
            cache_bytes=int(64e9),
            origin_latency_s=0.2,
        )
        res = run_strategy("cache_only", synth.source(window=65536), grid,
                           cfg, None, engine="interval")
        assert res.total_requests == n_requests
        return res

    run(1_000_000)
    peak1 = _peak_rss_mb()
    run(2_000_000)
    peak2 = _peak_rss_mb()
    assert peak2 - peak1 < 150.0, (peak1, peak2)
    assert peak2 < 2048.0, peak2
