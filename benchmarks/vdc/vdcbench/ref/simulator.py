"""The plain reference replay: per-chunk dict/heap discrete-event simulator
of the VDC delivery framework (paper §V-A1), predicting online.

Frozen copy of ``VDCSimulator`` (materialized replay), ``SimConfig``,
``RequestOutcome``, ``_OriginQueue`` and the WAN constants of
``src/repro/core/simulator.py``, and of ``make_prefetcher`` from
``src/repro/core/delivery.py``, at commit bcb7c9a; its ``md2`` builds the
copy in :mod:`.mining`.  It imports nothing of the program.
:func:`replay` returns every request's outcome and the ops the prediction
emitted for it, in trace order, and the replay's integer counters where it
stopped.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import itertools
import typing
from typing import Sequence

import numpy as np

from .cache import Cache, chunk_bytes, chunks_for_range, make_cache
from .hpm import HPMAdapter, NoPrefetch, PrefetchOp
from .mining import MD2Adapter
from .placement import PlacementEngine

GBPS = 1e9 / 8  # bytes per second per Gbps

DEFAULT_BANDWIDTH_GBPS = np.array(
    [
        #  srv   NA    AS    EU    SA    AF    OC
        [0.0, 15.0, 4.0, 8.0, 6.0, 4.0, 6.0],        # server ->
        [15.0, 0.0, 12.0, 25.0, 18.0, 10.0, 20.0],   # NA ->
        [4.0, 12.0, 0.0, 12.0, 8.0, 8.0, 14.0],      # Asia ->
        [8.0, 25.0, 12.0, 0.0, 14.0, 12.0, 14.0],    # Europe ->
        [6.0, 18.0, 8.0, 14.0, 0.0, 8.0, 8.0],       # S.America ->
        [4.0, 10.0, 8.0, 12.0, 8.0, 0.0, 8.0],       # Africa ->
        [6.0, 20.0, 14.0, 14.0, 8.0, 8.0, 0.0],      # Oceania ->
    ]
)

USER_LINK_GBPS = 100.0


@dataclasses.dataclass
class SimConfig:
    cache_policy: str = "lru"
    cache_bytes: int = 128 << 30
    n_service_procs: int = 10
    bandwidth_scale: float = 1.0
    traffic_scale: float = 1.0
    chunk_seconds: float = 3600.0
    stream_rate_bytes_per_s: float = 8e3
    enable_peer_cache: bool = True
    enable_placement: bool = True
    placement_period: float = 7 * 24 * 3600.0
    origin_latency_s: float = 2.0


class RequestOutcome(typing.NamedTuple):
    ts: float
    user_id: int
    bytes: int
    latency: float
    transfer_time: float
    local_bytes: int
    prefetched_bytes: int
    peer_bytes: int
    origin_bytes: int
    peer_time: float = 0.0


class _OriginQueue:
    """n service processes; returns (start_time, end_time) for a job."""

    def __init__(self, n_procs: int, overhead: float):
        self.free_at = [0.0] * n_procs
        self.overhead = overhead

    def submit(self, now: float, duration: float,
               with_overhead: bool = True) -> tuple[float, float]:
        i = int(np.argmin(self.free_at))
        start = max(now, self.free_at[i]) + (self.overhead if with_overhead else 0.0)
        end = start + duration
        self.free_at[i] = end
        return start, end


class VDCSimulator:
    """Replay a trace through the push-based delivery framework."""

    def __init__(self, grid, prefetcher, config: SimConfig,
                 use_cache: bool = True):
        self.grid = grid
        self.pf = prefetcher
        self.cfg = config
        self.use_cache = use_cache
        self.bw = DEFAULT_BANDWIDTH_GBPS * config.bandwidth_scale * GBPS
        self.n_dtn = self.bw.shape[0]
        self.caches: dict[int, Cache] = {
            d: make_cache(config.cache_policy, config.cache_bytes)
            for d in range(1, self.n_dtn)
        }
        self.origin = _OriginQueue(config.n_service_procs, config.origin_latency_s)
        self.placement = PlacementEngine(grid) if config.enable_placement else None
        self._prefetched: dict[tuple[int, tuple[int, int]], bool] = {}
        self._chunk_bytes = chunk_bytes(config.stream_rate_bytes_per_s,
                                        config.chunk_seconds)
        self._user_dtn: dict[int, int] = {}
        self._recent_requests: collections.deque = collections.deque(maxlen=5000)
        self._last_placement_ts = 0.0

    def _dtn_of(self, r) -> int:
        d = r.continent + 1
        self._user_dtn[r.user_id] = d
        return d

    def _available_chunks(self, r_or_op, now: float) -> list[tuple[int, int]]:
        tr_end = min(r_or_op.tr_end, now)    # data exists only up to `now`
        return chunks_for_range(r_or_op.obj, r_or_op.tr_start, tr_end,
                                self.cfg.chunk_seconds)

    def _transfer_time(self, nbytes: int, src: int, dst: int) -> float:
        if src == dst:
            return nbytes / (USER_LINK_GBPS * GBPS)
        bw = self.bw[src, dst]
        if bw <= 0:
            return float("inf")
        return nbytes / bw

    def run(self, requests: Sequence, stop=None) -> list[RequestOutcome]:
        """Outcomes of ``requests`` in trace order; ``stop(now)``, asked
        after each request, ends the replay early (earlier outcomes stand:
        later events cannot change them)."""
        cfg = self.cfg
        scale = 1.0 / cfg.traffic_scale
        events: list = []
        counter = itertools.count()
        for r in requests:
            heapq.heappush(events, (r.ts * scale, next(counter), "req", r))
        outcomes: list[RequestOutcome] = []
        stream_engine = getattr(self.pf, "streaming", None)

        while events:
            now, _, kind, payload = heapq.heappop(events)
            if kind == "push" and stream_engine is not None:
                self._apply_stream_push(payload)
                continue
            if kind == "prefetch":
                self._apply_prefetch(payload, now)
                continue
            r = dataclasses.replace(payload, ts=now)
            dtn = self._dtn_of(r)
            self._recent_requests.append(r)
            absorbed = bool(stream_engine and stream_engine.absorb(r))
            outcomes.append(self._serve(r, dtn, now, absorbed))
            for op in self.pf.observe(r):
                heapq.heappush(events, (max(now, op.issue_ts), next(counter),
                                        "prefetch", op))
            if stream_engine is not None:
                for push in stream_engine.pushes_until(now):
                    heapq.heappush(events, (push.ts, next(counter), "push", push))
            if (self.placement is not None
                    and now - self._last_placement_ts >= cfg.placement_period):
                self._run_placement(now)
                self._last_placement_ts = now
            if stop is not None and stop(now):
                break
        return outcomes

    def _serve(self, r, dtn: int, now: float, absorbed: bool) -> RequestOutcome:
        chunks = self._available_chunks(r, now)
        nbytes = r.size_bytes
        if not chunks or nbytes == 0:
            return RequestOutcome(now, r.user_id, 0, 0.0, 0.0, 0, 0, 0, 0)
        per_chunk = max(1, nbytes // len(chunks))
        local_b = pref_b = peer_b = origin_b = 0
        transfer = 0.0
        latency = 0.0
        cache = self.caches[dtn] if self.use_cache else None
        missing: list[tuple[int, int]] = []
        for ck in chunks:
            if cache is not None and cache.lookup(ck, per_chunk):
                key = (dtn, ck)
                if key in self._prefetched and not self._prefetched[key]:
                    self._prefetched[key] = True
                    pref_b += per_chunk
                else:
                    local_b += per_chunk
                transfer += per_chunk / (USER_LINK_GBPS * GBPS)
            else:
                missing.append(ck)
        still_missing: list[tuple[int, int]] = []
        peer_t = 0.0
        if missing and self.cfg.enable_peer_cache and self.use_cache:
            for ck in missing:
                src = self._find_peer(ck, dtn)
                if src is not None and self.bw[src, dtn] > self.bw[0, dtn]:
                    peer_b += per_chunk
                    dt_ = self._transfer_time(per_chunk, src, dtn)
                    transfer += dt_
                    peer_t += dt_
                    if cache is not None:
                        cache.insert(ck, per_chunk)
                else:
                    still_missing.append(ck)
        else:
            still_missing = missing
        if still_missing:
            ob = per_chunk * len(still_missing)
            if absorbed:
                transfer += ob / (USER_LINK_GBPS * GBPS)
                local_b += ob
            else:
                origin_b = ob
                duration = self._transfer_time(ob, 0, dtn)
                start, end = self.origin.submit(now, duration)
                latency = start - now
                transfer += end - start
                if cache is not None:
                    for ck in still_missing:
                        cache.insert(ck, per_chunk)
        return RequestOutcome(now, r.user_id, nbytes, latency, transfer,
                              local_b, pref_b, peer_b, origin_b, peer_t)

    def _find_peer(self, ck: tuple[int, int], dtn: int) -> int | None:
        best, best_bw = None, 0.0
        for d, cache in self.caches.items():
            if d == dtn or not cache.contains(ck):
                continue
            if self.bw[d, dtn] > best_bw:
                best, best_bw = d, self.bw[d, dtn]
        return best

    def _apply_prefetch(self, op: PrefetchOp, now: float) -> None:
        if not self.use_cache:
            return
        dtn = self._user_dtn.get(op.user_id)
        if dtn is None:
            return
        chunks = self._available_chunks(op, now)
        # pre-fetch ships only finalized chunks
        chunks = [ck for ck in chunks
                  if (ck[1] + 1) * self.cfg.chunk_seconds <= now]
        if not chunks:
            return
        cache = self.caches[dtn]
        new_chunks = [ck for ck in chunks if not cache.contains(ck)]
        if not new_chunks:
            return
        nbytes = self._chunk_bytes * len(new_chunks)
        duration = self._transfer_time(nbytes, 0, dtn)
        self.origin.submit(now, duration, with_overhead=False)
        for ck in new_chunks:
            cache.insert(ck, self._chunk_bytes)
            self._prefetched.setdefault((dtn, ck), False)

    def _apply_stream_push(self, push) -> None:
        if not self.use_cache:
            return
        chunks = chunks_for_range(push.obj, push.tr_start, push.tr_end,
                                  self.cfg.chunk_seconds)
        if not chunks:
            chunks = chunks_for_range(push.obj, push.tr_start,
                                      push.tr_start + self.cfg.chunk_seconds,
                                      self.cfg.chunk_seconds)
        nbytes = int((push.tr_end - push.tr_start)
                     * self.cfg.stream_rate_bytes_per_s)
        # one origin transfer serves all subscribed DTNs (request combining)
        self.origin.submit(push.ts, self._transfer_time(nbytes, 0, push.dtns[0])
                           if push.dtns else 0.0, with_overhead=False)
        for d in push.dtns:
            if d in self.caches:
                for ck in chunks:
                    self.caches[d].insert(ck, max(1, nbytes // len(chunks)))
                    self._prefetched.setdefault((d, ck), False)

    def _run_placement(self, now: float) -> None:
        if not self._recent_requests or not self.use_cache:
            return
        util = {d: 1.0 - c.used / max(1, c.capacity)
                for d, c in self.caches.items()}
        groups = self.placement.recluster(
            list(self._recent_requests), self._user_dtn, self.bw / GBPS, util)
        for g in groups:
            hub = g.hub_dtn
            if hub not in self.caches:
                continue
            for obj in g.hot_objs:
                recent = chunks_for_range(obj, max(0.0, now - 24 * 3600.0), now,
                                          self.cfg.chunk_seconds)
                new = [ck for ck in recent[-4:]
                       if not self.caches[hub].contains(ck)]
                for ck in new:
                    src = self._find_peer(ck, hub)
                    if src is None:
                        self.origin.submit(
                            now, self._transfer_time(self._chunk_bytes, 0, hub),
                            with_overhead=False)
                    self.caches[hub].insert(ck, self._chunk_bytes)
                    self._prefetched.setdefault((hub, ck), False)


def counters_of(caches: dict, stream_engine) -> dict:
    """The replay's integer counters as they stand: per DTN, its cache's
    hits, misses, hit and missed bytes, evictions and inserted bytes; and
    the stream pushes emitted."""
    out = {f"dtn{d}": (s.hits, s.misses, s.hit_bytes, s.miss_bytes,
                       s.evictions, s.inserted_bytes)
           for d, s in sorted((d, c.stats) for d, c in caches.items())}
    out["stream_pushes"] = (stream_engine.pushes_emitted
                            if stream_engine is not None else 0,)
    return out


def make_prefetcher(kind: str, grid, training_requests=None,
                    arima_dtype: str = "float32"):
    """The reference's prefetcher of ``kind``, built from the grid and the
    training split as the program's ``delivery.make_prefetcher`` builds
    it."""
    kind = kind.lower()
    if kind in ("none", "cache_only", "no_cache"):
        return NoPrefetch()
    if kind == "hpm":
        return HPMAdapter(training_requests, arima_dtype=arima_dtype)
    if kind == "md2":
        return MD2Adapter(grid.n_locs, training_requests,
                          arima_dtype=arima_dtype)
    raise ValueError(f"the reference has no prefetcher {kind!r}")


def replay(strategy: str, requests: Sequence, grid, config: SimConfig,
           training_requests=None, arima_dtype: str = "float32"):
    """Replay ``requests`` with ``strategy`` and stop right after the last
    one is served, before any later event; returns ``(outcomes, ops,
    counters)``: per request, its :class:`RequestOutcome` and the ops the
    prediction emitted for it, and :func:`counters_of` at that point.
    For ``hpm`` the ops are its model's, stream hand-offs included (the
    adapter turns those into subscriptions); for the others, what the
    adapter returns."""
    pf = make_prefetcher(strategy, grid, training_requests,
                         arima_dtype=arima_dtype)
    use_cache = strategy != "no_cache"
    if strategy in ("no_cache", "cache_only"):
        config = dataclasses.replace(config, enable_placement=False)
    ops: list[list[PrefetchOp]] = []
    emitter = pf.model if isinstance(pf, HPMAdapter) else pf
    observe = emitter.observe

    def logged(r):
        out = observe(r)
        ops.append(out)
        return out

    emitter.observe = logged
    served = itertools.count(1)
    n = len(requests)

    sim = VDCSimulator(grid, pf, config, use_cache=use_cache)
    outcomes = sim.run(requests, stop=lambda now: next(served) >= n)
    return (outcomes, ops[:len(outcomes)],
            counters_of(sim.caches, getattr(pf, "streaming", None)))
