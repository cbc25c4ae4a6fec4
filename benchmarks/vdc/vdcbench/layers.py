"""Span arithmetic shared by the per-layer metric readers.

A reader gets a context with ``window_s`` (the measured window, host
clock), ``spans`` (``(layer, start_s, end_s)`` from the layer wrappers in
:mod:`vdcbench.probes`), ``counters`` and ``trace`` (:func:`devtrace.extract`
output, or ``None``) with ``plane`` (the device plane of the first chip).
"""
from __future__ import annotations

from .devtrace import union


def seconds(ctx, layer: str) -> float:
    """Wall seconds covered by the spans of one layer."""
    return sum(e - s for s, e in
               union([(s, e) for n, s, e in ctx.spans if n == layer],
                     float("-inf"), float("inf")))


def seconds_within(ctx, inner: str, outer: str) -> float:
    """Wall seconds of ``inner`` spans that lie inside ``outer`` spans."""
    outer_iv = union([(s, e) for n, s, e in ctx.spans if n == outer],
                     float("-inf"), float("inf"))
    total = 0.0
    for n, s, e in ctx.spans:
        if n != inner:
            continue
        for a, b in outer_iv:
            lo, hi = max(s, a), min(e, b)
            if hi > lo:
                total += hi - lo
    return total


def covered(ctx) -> float:
    """Wall seconds covered by any layer span."""
    return sum(e - s for s, e in
               union([(s, e) for _, s, e in ctx.spans],
                     float("-inf"), float("inf")))


def share(ctx, secs: float) -> float | None:
    """Percent of the window; ``None`` (nothing to read) without spans."""
    if not ctx.spans or ctx.window_s <= 0:
        return None
    return 100.0 * secs / ctx.window_s
