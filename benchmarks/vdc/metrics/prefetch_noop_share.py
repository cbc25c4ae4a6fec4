"""Prefetch ops of the event loop and the final drain that found nothing
to fetch (every finalized chunk already cached, or none finalized), as a
percent of the prefetch ops applied, from the program's per-loop counters
``prefetch_noop`` and ``prefetch_events``."""
from vdcbench import program


def read(ctx):
    loops = program.spans(program.LOOPS)
    if loops is None or not any("prefetch_noop" in r.meta for r in loops):
        return None
    ops = sum(r.meta.get("prefetch_events", 0) for r in loops)
    noop = sum(r.meta.get("prefetch_noop", 0) for r in loops)
    return 100.0 * noop / ops if ops else None
