"""The integer audit tuple of a replay job.

Frozen copy of ``_counters()`` from ``benchmarks/bench_engine.py`` at
commit bcb7c9a: origin requests, prefetch chunks issued and used, stream
pushes, per-DTN hits, misses, evictions and inserted bytes, and the byte
split of the served requests.  Every engine and every window split of a
trace give the same tuple; the result line reports the first job's.
"""


def counters(res) -> tuple:
    agg = res.outcome_totals()
    return (res.origin_requests, res.prefetch_issued_chunks,
            res.prefetch_used_chunks, res.stream_pushes,
            tuple(sorted((d, s.hits, s.misses, s.evictions,
                          s.inserted_bytes)
                         for d, s in res.cache_stats.items())),
            agg.n, agg.bytes, agg.local_bytes, agg.prefetched_bytes,
            agg.peer_bytes, agg.origin_bytes)
