"""Spans and counters of the replay path, on the profiler's clock.

An operator who wants to see where a replay spends its time runs it under
``jax.profiler.trace(...)`` (or between ``start_trace`` and ``stop_trace``)
and then reads :func:`records`.  While a profiler trace runs, every span is
kept in memory and is also written into the trace as a
``jax.profiler.TraceAnnotation`` of the same name, so the program's layers
lie on the device trace's own clock.  With no trace running a span site
costs one profiler check (``TraceAnnotation.is_enabled``) and records
nothing.

Span names are ``vdc.<module>.<step>``:

- ``vdc.sim.job``: one ``run_strategy`` call; its ``meta`` holds the
  collector's pause time during the job (``gc_ns``, ``gc_pauses``);
- ``vdc.delivery.train``: building the prefetcher (FP-Growth training);
- ``vdc.engine.window``: one stream window of the vector engine, parent of
  ``vdc.engine.prep`` (request arrays, window prep, scaled requests),
  ``vdc.hpm.plan`` (with ``vdc.arima.flush``, one ARIMA bank flush:
  dispatch and the sync on its outputs), ``vdc.engine.loop`` (with
  ``vdc.engine.placement``) and ``vdc.engine.fold`` (folding the window's
  outcome columns);
- ``vdc.engine.drain``: the event heap drained after the last window;
- ``vdc.gc``: one generation-2 collector pause.

``vdc.engine.loop`` and ``vdc.engine.drain`` carry per-call time
accumulators in ``meta``, in nanoseconds: ``serve_ns`` (serving requests),
``prefetch_ns`` (applying prefetch ops), ``push_ns`` (applying stream
pushes) and ``stream_ns`` (the streaming engine's absorb, subscribe and
push emission).  They are taken by timed wrappers chosen once per loop, so
the loop carries no per-event branch; with a trace running each timed call
costs about two clock reads.

Counters are always on and coarse (per loop, per plan window, per bank
flush); their totals are in :func:`counters`, and while a trace runs they
are also in the ``meta`` of the span that counted them: ``requests``,
``prefetch_events``, ``prefetch_noop`` (prefetch ops that found every
finalized chunk cached, or none finalized), ``push_events`` and
``absorbed`` per loop or drain, ``gap_decisions`` (program-request
fast-path decisions) and ``gap_exact`` (those decided by ``_gap_stats``
inside the running sums' margin) per plan window, ``bank_calls``,
``bank_rows`` and ``bank_pad_rows`` per bank flush.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time

from jax.profiler import TraceAnnotation

enabled = TraceAnnotation.is_enabled

_clock = time.perf_counter_ns


@dataclasses.dataclass
class Span:
    """One recorded span; ``parent`` indexes :func:`records`."""

    name: str
    start_ns: int = 0
    end_ns: int = 0
    parent: int | None = None
    job: int | None = None
    window: int | None = None
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


_spans: list[Span] = []
_gc_spans: list[Span] = []
_stack: list[int] = []
_counters: collections.Counter = collections.Counter()
_jobs = 0
_job: int | None = None
_window: int | None = None


def records() -> list[Span]:
    """Every span recorded since the last :func:`reset`, in start order but
    for collector pauses, which come last."""
    return _spans + _gc_spans


def counters() -> dict[str, int]:
    """Counter totals since the last :func:`reset`."""
    return dict(_counters)


def reset() -> None:
    global _jobs, _job, _window
    _spans.clear()
    _gc_spans.clear()
    _stack.clear()
    _counters.clear()
    _jobs, _job, _window = 0, None, None


class _Off:
    """The span site when no trace runs: counters only."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    @staticmethod
    def timed(key: str, fn):
        return fn

    @staticmethod
    def count(**counts: int) -> None:
        _counters.update(counts)


_OFF = _Off()


class _Recording:
    __slots__ = ("rec", "_note", "_acc")

    def __init__(self, name: str):
        self.rec = Span(name)
        self._note = None
        self._acc: dict[str, list] = {}

    def __enter__(self):
        rec = self.rec
        self._note = TraceAnnotation(rec.name)
        self._note.__enter__()
        rec.parent = _stack[-1] if _stack else None
        rec.job, rec.window = _job, _window
        _stack.append(len(_spans))
        _spans.append(rec)
        rec.start_ns = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        rec.end_ns = _clock()
        _stack.pop()
        for key, acc in self._acc.items():
            rec.meta[key] = acc[0]
        if rec.meta:
            self._note.set_metadata(**rec.meta)
        self._note.__exit__(*exc)
        return False

    def timed(self, key: str, fn):
        """``fn`` with its wall time added to the accumulator ``key``."""
        acc = self._acc.setdefault(key, [0])
        clock = _clock

        def call(*args):
            t0 = clock()
            out = fn(*args)
            acc[0] += clock() - t0
            return out

        return call

    def count(self, **counts: int) -> None:
        _counters.update(counts)
        meta = self.rec.meta
        for key, n in counts.items():
            meta[key] = meta.get(key, 0) + n


def span(name: str):
    """A span around a ``with`` block: recorded only while a profiler trace
    runs.  The object bound by ``as`` has ``timed(key, fn)`` and
    ``count(**counts)``, which work whether or not it records."""
    if not enabled():
        return _OFF
    return _Recording(name)


class _Window(_Recording):
    __slots__ = ("_index", "_outer")

    def __init__(self, index: int):
        super().__init__("vdc.engine.window")
        self._index = index

    def __enter__(self):
        global _window
        self._outer, _window = _window, self._index
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        global _window
        super().__exit__(*exc)
        _window = self._outer
        return False


def window(index: int):
    """The span of stream window ``index``; spans inside it carry the
    index."""
    if not enabled():
        return _OFF
    return _Window(index)


# -- collector pauses --------------------------------------------------------

class _Pauses:
    """Collector pause time, counted while a job records."""

    ns = count = start_ns = 0
    rec: Span | None = None
    note: TraceAnnotation | None = None


_pauses = _Pauses()


def _gc_hook(phase: str, info: dict) -> None:
    p = _pauses
    if phase == "start":
        p.start_ns = _clock()
        if info["generation"] == 2:
            p.note = TraceAnnotation("vdc.gc")
            p.note.__enter__()
            p.rec = Span("vdc.gc", p.start_ns, 0,
                         _stack[-1] if _stack else None, _job, _window)
        return
    end = _clock()
    p.ns += end - p.start_ns
    p.count += 1
    if p.rec is not None:
        p.rec.end_ns = end
        _gc_spans.append(p.rec)
        p.note.__exit__(None, None, None)
        p.rec = p.note = None


class _Job(_Recording):
    __slots__ = ("_outer", "_gc0", "_hooked")

    def __init__(self):
        super().__init__("vdc.sim.job")

    def __enter__(self):
        global _jobs, _job, _window
        _jobs += 1
        self._outer = (_job, _window)
        _job, _window = _jobs, None
        self._hooked = _gc_hook not in gc.callbacks
        if self._hooked:
            gc.callbacks.append(_gc_hook)
        self._gc0 = (_pauses.ns, _pauses.count)
        return super().__enter__()

    def __exit__(self, *exc) -> bool:
        global _job, _window
        self.rec.meta["gc_ns"] = _pauses.ns - self._gc0[0]
        self.rec.meta["gc_pauses"] = _pauses.count - self._gc0[1]
        super().__exit__(*exc)
        if self._hooked:
            gc.callbacks.remove(_gc_hook)
        _job, _window = self._outer
        return False


def job():
    """The span of one replay job.  While it records, the collector's
    pauses are timed (all generations, into the job's ``gc_ns``) and each
    generation-2 pause becomes a ``vdc.gc`` span."""
    if not enabled():
        return _OFF
    return _Job()
