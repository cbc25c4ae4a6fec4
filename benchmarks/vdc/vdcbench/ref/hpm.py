"""Hybrid Pre-fetching Model, online (paper §IV-A), and its delivery adapter.

Frozen copy of the online half of ``src/repro/core/hpm.py`` (classification
state machine, history / rules / stream predictions, ``HybridPrefetcher``)
and of ``HPMAdapter`` and ``NoPrefetch`` from ``src/repro/core/delivery.py``,
at commit bcb7c9a.  Every request is observed one at a time; every history
forecast is one padded bank call.

``arima_dtype`` selects the bank's precision (``float32`` as configured;
``bfloat16`` for the correctness control).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Iterable, Sequence

import numpy as np

from .arima import ARIMA, predict_next_timestamp
from .fpgrowth import RulePredictor
from .streaming import StreamingEngine

WEEK = 7 * 24 * 3600.0
REALTIME_PERIOD = 120.0     # seconds; <= this inter-arrival => real-time
LEARNING_PERIOD = WEEK
REPEAT_THRESHOLD = 3
PREFETCH_OFFSET = 0.8
TOP_N_HUMAN = 3


@dataclasses.dataclass(frozen=True)
class PrefetchOp:
    """One planned pre-fetch: push (obj, [tr_start, tr_end]) toward user at
    time ``issue_ts``."""

    issue_ts: float
    user_id: int
    obj: int
    tr_start: float
    tr_end: float
    reason: str      # "history" | "rules" | "stream"


@dataclasses.dataclass
class _UserState:
    timestamps: list[float] = dataclasses.field(default_factory=list)
    objs: collections.Counter = dataclasses.field(
        default_factory=collections.Counter)
    recent_objs: list[int] = dataclasses.field(default_factory=list)
    last_window: float = 0.0
    first_ts: float = 0.0
    pattern_repeats: int = 0
    classified: str = "unknown"     # unknown | program | realtime | human
    last_cycle_objs: frozenset = frozenset()
    cycle_objs: set = dataclasses.field(default_factory=set)
    cycle_start: float = 0.0


def _observe_classification(st: _UserState, r) -> None:
    """Online classification (paper §IV-A2): one request into the user's
    state machine."""
    if not st.timestamps:
        st.first_ts = r.ts
        st.cycle_start = r.ts
    st.timestamps.append(r.ts)
    if len(st.timestamps) > 200:
        del st.timestamps[:100]
    st.objs[r.obj] += 1
    st.recent_objs.append(r.obj)
    if len(st.recent_objs) > 16:
        del st.recent_objs[0]
    st.last_window = r.tr_end - r.tr_start

    if st.classified in ("program", "realtime"):
        return
    st.cycle_objs.add(r.obj)
    if st.last_cycle_objs and r.obj in st.last_cycle_objs and \
            st.cycle_objs >= st.last_cycle_objs:
        st.pattern_repeats += 1
        st.last_cycle_objs = frozenset(st.cycle_objs)
        st.cycle_objs = set()
    elif not st.last_cycle_objs and len(st.timestamps) >= 2 and \
            r.obj in st.cycle_objs and len(st.cycle_objs) >= 1:
        st.last_cycle_objs = frozenset(st.cycle_objs)
        st.cycle_objs = set()
    if st.pattern_repeats >= REPEAT_THRESHOLD and \
            (r.ts - st.first_ts) <= LEARNING_PERIOD * 2:
        gaps = np.diff(np.array(sorted(set(st.timestamps))[-12:]))
        period = float(np.median(gaps)) if gaps.size else float("inf")
        st.classified = "realtime" if period <= REALTIME_PERIOD else "program"
    elif (r.ts - st.first_ts) > LEARNING_PERIOD and st.pattern_repeats == 0:
        st.classified = "human"


class HybridPrefetcher:
    """Online HPM: observe requests one at a time, emit pre-fetch plans."""

    def __init__(self, rule_transactions=None, min_support: int = 30,
                 min_confidence: float = 0.5, offset: float = PREFETCH_OFFSET,
                 arima_history: int = 60, arima_dtype: str = "float32"):
        self.offset = offset
        self.arima = ARIMA(n=arima_history, dtype=arima_dtype)
        self.users: dict[int, _UserState] = collections.defaultdict(_UserState)
        self.rule_predictor = (
            RulePredictor(rule_transactions, min_support, min_confidence)
            if rule_transactions else None)
        self.realtime_subscriptions: set[tuple[int, int]] = set()

    def observe(self, r) -> list[PrefetchOp]:
        """Feed one request; return pre-fetch ops to schedule now."""
        st = self.users[r.user_id]
        _observe_classification(st, r)
        if st.classified == "realtime":
            key = (r.user_id, r.obj)
            if key not in self.realtime_subscriptions:
                self.realtime_subscriptions.add(key)
                return [PrefetchOp(r.ts, r.user_id, r.obj, r.tr_end,
                                   r.tr_end + st.last_window, "stream")]
            return []
        if st.classified == "program":
            return self._predict_history(st, r)
        if st.classified == "human":
            return self._predict_rules(st, r)
        return []

    def _predict_history(self, st: _UserState, r) -> list[PrefetchOp]:
        ts_hist = np.array(sorted(set(st.timestamps)))
        if ts_hist.size < 4:
            return []
        next_ts = predict_next_timestamp(ts_hist, self.arima)
        issue = r.ts + self.offset * max(0.0, next_ts - r.ts)
        width = st.last_window
        return [PrefetchOp(issue, r.user_id, int(obj), next_ts - width,
                           next_ts, "history")
                for obj in sorted(st.last_cycle_objs or {r.obj})]

    def _predict_rules(self, st: _UserState, r) -> list[PrefetchOp]:
        if self.rule_predictor is None:
            return []
        preds = self.rule_predictor.predict(st.recent_objs, top_n=TOP_N_HUMAN)
        if not preds:
            return []
        ts = st.timestamps
        gap = (ts[-1] - ts[-2]) if len(ts) >= 2 else 300.0
        next_ts = r.ts + gap
        issue = r.ts + self.offset * max(0.0, next_ts - r.ts)
        return [PrefetchOp(issue, r.user_id, int(obj), r.tr_start, r.tr_end,
                           "rules") for obj in preds]


def build_rule_transactions(requests: Iterable, session_seconds: float = 3600.0
                            ) -> list[list[int]]:
    """Sessionize a training trace into FP-Growth transactions."""
    sessions: dict[tuple[int, int], list[int]] = collections.defaultdict(list)
    for r in requests:
        sessions[(r.user_id, int(r.ts // session_seconds))].append(r.obj)
    return [list(dict.fromkeys(v)) for v in sessions.values()]


class NoPrefetch:
    name = "none"

    def observe(self, r) -> list[PrefetchOp]:
        return []


class HPMAdapter:
    """The paper's Hybrid Pre-fetching Model: stream ops become streaming
    subscriptions, the rest are scheduled pre-fetches."""

    name = "hpm"

    def __init__(self, training_requests: Sequence | None = None,
                 arima_dtype: str = "float32"):
        txs = (build_rule_transactions(training_requests)
               if training_requests else None)
        self.model = HybridPrefetcher(rule_transactions=txs,
                                      arima_dtype=arima_dtype)
        self.streaming = StreamingEngine()

    def observe(self, r) -> list[PrefetchOp]:
        ops = self.model.observe(r)
        out = []
        for op in ops:
            if op.reason == "stream":
                self.streaming.subscribe(r.user_id, r.continent + 1, r.obj,
                                         max(1.0, op.tr_end - op.tr_start),
                                         r.ts)
            else:
                out.append(op)
        return out

