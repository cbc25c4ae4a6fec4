"""Byte-budget chunk caches with LRU / LFU eviction (paper §IV-C1).

Frozen copy of the dict/heap caches of ``src/repro/core/cache.py`` at
commit bcb7c9a, the readable half of that module.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
import math
from typing import Hashable, Iterator

ChunkKey = tuple[int, int]          # (obj, chunk_index)


def chunks_for_range(obj: int, tr_start: float, tr_end: float,
                     chunk_seconds: float) -> list[ChunkKey]:
    """Chunk keys covering [tr_start, tr_end) for a data object."""
    if tr_end <= tr_start:
        return []
    first = int(math.floor(tr_start / chunk_seconds))
    last = int(math.ceil(tr_end / chunk_seconds))
    return [(obj, c) for c in range(first, last)]


def chunk_bytes(rate_bytes_per_s: float, chunk_seconds: float) -> int:
    return int(rate_bytes_per_s * chunk_seconds)


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    hit_bytes: int = 0
    miss_bytes: int = 0
    evictions: int = 0
    inserted_bytes: int = 0


class Cache:
    """Interface: a byte-budget key->size cache."""

    def __init__(self, capacity_bytes: int):
        self.capacity = int(capacity_bytes)
        self.used = 0
        self.stats = CacheStats()

    def lookup(self, key: Hashable, size: int) -> bool:
        if self.contains(key):
            self.stats.hits += 1
            self.stats.hit_bytes += size
            self._touch(key)
            return True
        self.stats.misses += 1
        self.stats.miss_bytes += size
        return False

    def insert(self, key: Hashable, size: int) -> None:
        if size > self.capacity:
            return
        if self.contains(key):
            self._touch(key)
            return
        while self.used + size > self.capacity:
            self._evict_one()
            self.stats.evictions += 1
        self._insert(key, size)
        self.used += size
        self.stats.inserted_bytes += size

    def contains(self, key: Hashable) -> bool:
        raise NotImplementedError

    def _touch(self, key: Hashable) -> None:
        raise NotImplementedError

    def _insert(self, key: Hashable, size: int) -> None:
        raise NotImplementedError

    def _evict_one(self) -> None:
        raise NotImplementedError

    def keys(self) -> Iterator[Hashable]:
        raise NotImplementedError


class LRUCache(Cache):
    def __init__(self, capacity_bytes: int):
        super().__init__(capacity_bytes)
        self._od: collections.OrderedDict[Hashable, int] = collections.OrderedDict()

    def contains(self, key):
        return key in self._od

    def _touch(self, key):
        self._od.move_to_end(key)

    def _insert(self, key, size):
        self._od[key] = size

    def _evict_one(self):
        key, size = self._od.popitem(last=False)
        self.used -= size

    def keys(self):
        return iter(self._od.keys())


class LFUCache(Cache):
    """LFU with a lazy min-heap of (freq, seq, key)."""

    def __init__(self, capacity_bytes: int):
        super().__init__(capacity_bytes)
        self._sizes: dict[Hashable, int] = {}
        self._freq: dict[Hashable, int] = {}
        self._heap: list[tuple[int, int, Hashable]] = []
        self._seq = 0

    def contains(self, key):
        return key in self._sizes

    def _touch(self, key):
        self._freq[key] += 1
        self._seq += 1
        heapq.heappush(self._heap, (self._freq[key], self._seq, key))

    def _insert(self, key, size):
        self._sizes[key] = size
        self._freq[key] = 1
        self._seq += 1
        heapq.heappush(self._heap, (1, self._seq, key))

    def _evict_one(self):
        while self._heap:
            freq, _, key = heapq.heappop(self._heap)
            if key in self._sizes and self._freq.get(key) == freq:
                self.used -= self._sizes.pop(key)
                del self._freq[key]
                return
        raise RuntimeError("evict from empty LFU cache")

    def keys(self):
        return iter(self._sizes.keys())


def make_cache(policy: str, capacity_bytes: int) -> Cache:
    policy = policy.lower()
    if policy == "lru":
        return LRUCache(capacity_bytes)
    if policy == "lfu":
        return LFUCache(capacity_bytes)
    raise ValueError(f"unknown cache policy: {policy}")
