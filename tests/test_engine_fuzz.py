"""Three-engine differential fuzz harness.

Small random replay scenarios — object grids, request interleavings,
live-tail and zero-byte edge cases, chunk granularities from sub-minute to
coarse, cache budgets from thrash to no-pressure, and random peer topologies
(including dead links and bandwidth ties) — are replayed through all three
engines.  Integer counters must match the reference engine exactly; this is
the randomized half of the equivalence contract pinned by
``tests/test_engine_equivalence.py``.

The harness has two generation front-ends over ONE scenario grammar
(:func:`gen_scenario`, driven by a seeded ``random.Random``):

- a **deterministic sweep** that needs only the standard library, in two
  profiles: fast (``FAST_EXAMPLES`` scenarios per strategy, tier-1) and deep
  (``DEEP_EXAMPLES`` ≥ 200 scenarios per strategy, ``slow``-marked for the
  CI ``fuzz`` job);
- a **hypothesis-driven** adaptive profile (also ``slow``-marked) that
  explores the same grammar with shrinking, when hypothesis is installed.

Everything is derandomized: scenario ``i`` of a sweep derives from
``FUZZ_SEED + i`` only, and the hypothesis profile runs with
``derandomize=True`` seeded by ``FUZZ_SEED``, so any divergence reproduces
from this file alone.
"""
import random

import numpy as np
import pytest

from repro.core import SimConfig, run_strategy
from repro.core.simulator import DEFAULT_BANDWIDTH_GBPS
from repro.core.trace import (ObjectGrid, Request, RequestList,
                              StreamingRequestSource)

#: derandomized fuzz seed — recorded here per the acceptance criteria; any
#: divergence reproduces with this seed alone (no hypothesis DB needed)
FUZZ_SEED = 20260808

FAST_EXAMPLES = 12
DEEP_EXAMPLES = 220

STRATEGIES = ("no_cache", "cache_only", "md1", "md2", "hpm")

_U = 1 << 20


def _int_counters(res):
    # outcome_totals() sums per-request outcomes for materialized runs and
    # returns the already-folded OutcomeAggregate for streamed runs, so the
    # same tuple compares both input paths
    agg = res.outcome_totals()
    return (
        res.origin_requests,
        res.total_requests,
        res.prefetch_issued_chunks,
        res.prefetch_used_chunks,
        res.stream_pushes,
        tuple(sorted(
            (d, s.hits, s.misses, s.hit_bytes, s.miss_bytes, s.evictions,
             s.inserted_bytes)
            for d, s in res.cache_stats.items())),
        agg.local_bytes,
        agg.prefetched_bytes,
        agg.peer_bytes,
        agg.origin_bytes,
        agg.bytes,
    )


# ---------------------------------------------------------------------------
# scenario grammar (shared by the deterministic sweep and hypothesis)
# ---------------------------------------------------------------------------


def gen_bandwidth(rng: random.Random):
    """7x7 link matrix with deliberate edge cases: dead links, links slower
    and faster than the origin row, and exact bandwidth ties (the §IV-D
    tie-break: max bandwidth, lowest DTN id)."""
    if rng.random() < 0.5:
        return None                       # paper's calibrated default matrix
    n = DEFAULT_BANDWIDTH_GBPS.shape[0]
    levels = [0.0, 2.0, 8.0, 8.0, 25.0, 100.0]
    bw = np.array([[rng.choice(levels) for _ in range(n)] for _ in range(n)])
    np.fill_diagonal(bw, 100.0)
    return bw


def gen_trace(rng: random.Random):
    """A short request interleaving over a small object grid.

    Time ranges use minute-scale numbers so that the drawn chunk
    granularities span one-chunk requests up to a few hundred chunks per
    request (crossing the interval engine's sweep/block planner threshold
    both ways)."""
    grid = ObjectGrid(rng.randint(1, 2), rng.randint(1, 3))
    n = rng.randint(4, 28)
    reqs = []
    ts = 0.0
    for _ in range(n):
        ts += rng.uniform(0.5, 900.0)
        tr_start = rng.uniform(0.0, 4000.0)
        width = rng.uniform(0.0, 3000.0)
        # live-tail edge case: a range reaching past the request timestamp
        # is clamped to ``now`` by every engine
        if rng.random() < 0.5:
            tr_start = max(0.0, ts - width * rng.uniform(0.2, 1.5))
        roll = rng.random()
        if roll < 0.1:
            size = 0                                  # zero-byte request
        elif roll < 0.3:
            size = rng.randint(1, 64)                 # sub-chunk sizes
        else:
            size = rng.randint(1, 48) * _U
        reqs.append(Request(
            ts=ts,
            user_id=rng.randint(1, 4),
            obj=rng.randint(0, grid.n_objects - 1),
            tr_start=tr_start,
            tr_end=tr_start + width,
            size_bytes=size,
            continent=rng.randint(0, 5),
        ))
    return grid, RequestList(reqs)


def gen_scenario(rng: random.Random):
    grid, trace = gen_trace(rng)
    cfg_kw = dict(
        cache_policy=rng.choice(["lru", "lru", "lru", "lfu"]),
        cache_bytes=rng.choice([64 * _U, 8 * _U, 2 * _U, 512 << 10]),
        chunk_seconds=rng.choice([7.0, 30.0, 120.0, 900.0]),
        stream_rate_bytes_per_s=8e3,
        enable_peer_cache=rng.random() < 0.75,
        origin_latency_s=rng.choice([0.0, 2.0]),
        bandwidth_gbps=gen_bandwidth(rng),
        traffic_scale=rng.choice([1.0, 1.0, 2.0]),
    )
    return grid, trace, cfg_kw


def check_strategy(strategy, grid, trace, cfg_kw, pin, window=None):
    """Replay one scenario through every engine (and, for static LRU
    serving, through every interval route, pinned with ``pin`` — the
    ``interval_route`` fixture) and compare counters — then do it again
    through the windowed streaming source (``window`` requests at a time;
    randomized by the sweeps), which must match bit-for-bit."""
    # ``interval_flat_state`` defaults to True, so the plain interval run
    # already sweeps the flat array-backed store; the False run pins the
    # Python-list reference store to the same counters (PR 7 bugfix bar)
    runs = [("vector", {}, None), ("interval", {}, None),
            ("interval", {"interval_flat_state": False}, None)]
    if strategy == "cache_only" and cfg_kw["cache_policy"] == "lru":
        # pin both interval routes besides the engine's own choice: the
        # interval sweep and the fused block replay
        runs += [("interval", {}, "sweep"), ("interval", {}, "fused")]
    ref = run_strategy(strategy, trace, grid,
                       SimConfig(**cfg_kw), None, engine="reference")
    want = _int_counters(ref)
    for engine, extra, route in runs:
        with pin(route):
            res = run_strategy(strategy, trace, grid,
                               SimConfig(**cfg_kw, **extra), None,
                               engine=engine)
        got = _int_counters(res)
        assert got == want, (
            f"{engine} engine ({extra or route or 'default'}) diverged from "
            f"the reference under {strategy}: {got} != {want}")
    w = window or max(1, len(trace) // 3)
    src = StreamingRequestSource.from_requests(trace, window=w)
    for engine, extra, route in [("reference", {}, None)] + runs:
        with pin(route):
            res = run_strategy(strategy, src, grid,
                               SimConfig(**cfg_kw, **extra), None,
                               engine=engine)
        got = _int_counters(res)
        assert got == want, (
            f"{engine} engine ({extra or route or 'default'}) streamed with "
            f"window={w} diverged from the reference under {strategy}: "
            f"{got} != {want}")


def _sweep(strategy: str, n_examples: int, pin) -> None:
    for i in range(n_examples):
        rng = random.Random((FUZZ_SEED, strategy, i).__repr__())
        grid, trace, cfg_kw = gen_scenario(rng)
        # drawn after the scenario so existing recorded scenarios replay
        # identically; width 1 forces a window per request
        window = rng.choice((1, 2, 3, 5, 9, 17))
        try:
            check_strategy(strategy, grid, trace, cfg_kw, pin, window=window)
        except AssertionError as e:
            raise AssertionError(
                f"scenario {i} (seed base {FUZZ_SEED}) of strategy "
                f"{strategy}: {e}") from e


# ---------------------------------------------------------------------------
# deterministic sweeps
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fuzz_engines_agree_fast(strategy, interval_route):
    _sweep(strategy, FAST_EXAMPLES, interval_route)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fuzz_engines_agree_deep(strategy, interval_route):
    _sweep(strategy, DEEP_EXAMPLES, interval_route)


THRASH_EXAMPLES = 10


@pytest.mark.parametrize("strategy", ("cache_only", "md1", "hpm"))
def test_fuzz_thrash_regime(strategy, interval_route):
    """Eviction-thrash sweep: the cache is pinned to a few requests' worth
    of bytes so nearly every fused block runs the speculative eviction
    planner's full lifecycle — plan, truncate, incremental re-plan,
    invalidate-on-commit — and the vector engine's batched plan consume.
    LRU is pinned so the cache_only leg sweeps all interval routes."""
    for i in range(THRASH_EXAMPLES):
        rng = random.Random((FUZZ_SEED, "thrash", strategy, i).__repr__())
        grid, trace, cfg_kw = gen_scenario(rng)
        cfg_kw["cache_policy"] = "lru"
        cfg_kw["cache_bytes"] = rng.choice([128 << 10, 256 << 10, _U])
        window = rng.choice((1, 3, 7, 17))
        try:
            check_strategy(strategy, grid, trace, cfg_kw, interval_route,
                           window=window)
        except AssertionError as e:
            raise AssertionError(
                f"thrash scenario {i} (seed base {FUZZ_SEED}) of strategy "
                f"{strategy}: {e}") from e


PHASED_EXAMPLES = 10


def gen_phased_scenario(rng: random.Random):
    """Scenario whose total insert volume exceeds cache capacity by a drawn
    2-8x factor: the fused engines must span blocks with mid-block eviction
    phases (the phased block replay) instead of collapsing to request-sized
    truncated blocks, while the drawn range overlaps exercise the
    legal-victim invariant (a key re-referenced later in the block must
    never be evicted at an earlier phase boundary)."""
    grid = ObjectGrid(1, rng.randint(1, 2))
    n = rng.randint(36, 90)
    reqs = []
    ts = 0.0
    total = 0
    for _ in range(n):
        ts += rng.uniform(0.5, 60.0)
        tr_start = rng.uniform(0.0, 4000.0)
        width = rng.uniform(30.0, 600.0)
        if rng.random() < 0.4:
            # live-tail edge case under pressure
            tr_start = max(0.0, ts - width * rng.uniform(0.2, 1.5))
        size = rng.randint(1, 24) * _U
        total += size
        reqs.append(Request(
            ts=ts,
            user_id=rng.randint(1, 3),
            obj=rng.randint(0, grid.n_objects - 1),
            tr_start=tr_start,
            tr_end=tr_start + width,
            size_bytes=size,
            continent=rng.randint(0, 2),
        ))
    cfg_kw = dict(
        cache_policy="lru",
        cache_bytes=max(256 << 10, total // rng.randint(2, 8)),
        chunk_seconds=rng.choice([7.0, 30.0, 120.0]),
        stream_rate_bytes_per_s=8e3,
        enable_peer_cache=rng.random() < 0.75,
        origin_latency_s=rng.choice([0.0, 2.0]),
        bandwidth_gbps=gen_bandwidth(rng),
        traffic_scale=1.0,
    )
    return grid, RequestList(reqs), cfg_kw


@pytest.mark.parametrize("strategy", ("cache_only", "md1"))
def test_fuzz_phased_eviction(strategy, interval_route):
    """Derandomized phased-eviction sweep: capacity drawn below the trace's
    insert volume so blocks are forced to span 2-8x the cache.  LRU is
    pinned, so the cache_only leg also sweeps both pinned interval routes
    via :func:`check_strategy`."""
    for i in range(PHASED_EXAMPLES):
        rng = random.Random((FUZZ_SEED, "phased", strategy, i).__repr__())
        grid, trace, cfg_kw = gen_phased_scenario(rng)
        window = rng.choice((5, 9, 17))
        try:
            check_strategy(strategy, grid, trace, cfg_kw, interval_route,
                           window=window)
        except AssertionError as e:
            raise AssertionError(
                f"phased scenario {i} (seed base {FUZZ_SEED}) of strategy "
                f"{strategy}: {e}") from e


def _churn_trace(n_ranges: int, rereference: bool):
    """13+ disjoint 8-chunk ranges over one object, 1 MiB per chunk; with
    ``rereference`` the final request re-touches the first range's keys."""
    cs = 60.0
    reqs = []
    ts = 0.0

    def add(lo_chunk: int, n_chunks: int) -> None:
        nonlocal ts
        ts += 10_000.0      # keep every range safely in the past (no clamp)
        reqs.append(Request(
            ts=ts, user_id=1, obj=0,
            tr_start=lo_chunk * cs, tr_end=(lo_chunk + n_chunks) * cs,
            size_bytes=n_chunks * _U, continent=0,
        ))

    for k in range(n_ranges):
        add(8 * k, 8)
    if rereference:
        add(0, 8)
    return ObjectGrid(1, 1), RequestList(reqs)


_CHURN_CFG = dict(cache_policy="lru", cache_bytes=8 * _U, chunk_seconds=60.0,
                  stream_rate_bytes_per_s=8e3, enable_peer_cache=False,
                  origin_latency_s=0.0, traffic_scale=1.0)


def test_phased_block_spans_capacity():
    """Pure-churn block (13 disjoint capacity-sized ranges, no re-touch):
    the phased engines must replay it as ONE block with mid-block eviction
    phases — visible in the new telemetry — and match the reference."""
    grid, trace = _churn_trace(13, rereference=False)
    ref = run_strategy("cache_only", trace, grid, SimConfig(**_CHURN_CFG),
                       None, engine="reference")
    want = _int_counters(ref)
    for engine in ("interval", "vector"):
        res = run_strategy("cache_only", trace, grid,
                           SimConfig(**_CHURN_CFG), None, engine=engine)
        assert _int_counters(res) == want, engine
        assert res.block_phases >= 4, (engine, res.block_phases)
        assert res.inblock_victims >= 4, (engine, res.inblock_victims)


def test_inblock_victim_rereference(interval_route):
    """In-block-victim re-reference regression: the first range's keys are
    re-touched by the LAST request of the block, so at every earlier phase
    boundary they are ineligible victims (the suffix-blocked plan must
    skip them), even though the reference — with no lookahead — evicts
    them and serves the re-touch as a miss.  Exact counter equality across
    every engine and route is the bar."""
    grid, trace = _churn_trace(13, rereference=True)
    check_strategy("cache_only", grid, trace, _CHURN_CFG, interval_route,
                   window=5)


# ---------------------------------------------------------------------------
# hypothesis-driven adaptive profile (CI fuzz job)
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, seed, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @pytest.mark.slow
    @pytest.mark.parametrize("strategy", STRATEGIES)
    @seed(FUZZ_SEED)
    @settings(max_examples=DEEP_EXAMPLES, derandomize=True, deadline=None,
              suppress_health_check=[HealthCheck.too_slow])
    @given(sub_seed=st.integers(0, 2**48))
    def test_fuzz_engines_agree_hypothesis(strategy, sub_seed,
                                           interval_route):
        """Same grammar, hypothesis-chosen seeds (with shrinking to the
        smallest failing sub-seed on divergence)."""
        rng = random.Random((FUZZ_SEED, strategy, sub_seed).__repr__())
        grid, trace, cfg_kw = gen_scenario(rng)
        window = rng.choice((1, 2, 3, 5, 9, 17))
        check_strategy(strategy, grid, trace, cfg_kw, interval_route,
                       window=window)
