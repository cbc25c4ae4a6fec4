"""The program's own spans and counters, for the per-layer readers.

The replay records them through ``repro.core.telemetry`` while a profiler
trace runs, which in this benchmark is the traced run's measured window.
Every function here returns ``None`` where the program has no such module
(an older checkout) or recorded nothing, so its readers report nothing
there.
"""
from __future__ import annotations

LOOPS = ("vdc.engine.loop", "vdc.engine.drain")
TIMED = ("serve_ns", "prefetch_ns", "push_ns", "stream_ns")


def records() -> list:
    try:
        from repro.core import telemetry
    except ImportError:
        return []
    return telemetry.records()


def spans(names) -> list | None:
    """The recorded spans with these names, or ``None`` without any."""
    return [r for r in records() if r.name in names] or None


def seconds(names) -> float | None:
    """Wall seconds of the spans with these names."""
    found = spans(names)
    if found is None:
        return None
    return sum(r.end_ns - r.start_ns for r in found) / 1e9


def meta_sum(names, keys) -> float | None:
    """The sum of these ``meta`` entries over the spans with these names."""
    found = spans(names)
    if found is None:
        return None
    return float(sum(r.meta.get(k, 0) for r in found for k in keys))


def meta_seconds(names, key: str) -> float | None:
    """A nanosecond ``meta`` entry summed over the spans with these names,
    in seconds."""
    ns = meta_sum(names, (key,))
    return None if ns is None else ns / 1e9


def loop_self_seconds() -> float | None:
    """``vdc.engine.loop`` spans minus their timed calls and their
    ``vdc.engine.placement`` children."""
    recs = records()
    loops = {i: r for i, r in enumerate(recs) if r.name == "vdc.engine.loop"}
    if not loops:
        return None
    ns = sum(r.end_ns - r.start_ns - sum(r.meta.get(k, 0) for k in TIMED)
             for r in loops.values())
    ns -= sum(r.end_ns - r.start_ns for r in recs
              if r.name == "vdc.engine.placement" and r.parent in loops)
    return ns / 1e9


def share(ctx, secs: float | None) -> float | None:
    """Percent of the window."""
    if secs is None or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
