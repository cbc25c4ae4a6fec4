"""MD2, the paper's mining baseline (Xiong et al., 2016; arXiv:2012.15321
§V-B): regional-mesh association rules for what to fetch, ARIMA over each
user's access times for when, applied to every request.

Frozen copy of ``MeshRulePredictor`` (``src/repro/core/mining.py``) and
``MD2Adapter.observe`` (``src/repro/core/delivery.py``) at commit 7d95768.
It imports nothing of the program: the grid's ``loc_of`` is copied as
``obj % n_locs``, the rules come from :mod:`.fpgrowth`.

One departure from the program at that commit: the timestamp forecast runs
through the fixed-width ARIMA bank (:mod:`.arima`, the program's
``ARIMA(bank=True)``), where the program's model uses the single-series
program (``ARIMA(n=60, bank=False)``).  The bank is what MD2 must run once
it is planned in windows: only the bank's row independence makes a batched
op stream equal to online prediction, op for op.  MD2's own rules are kept:
a history of 4 or more distinct timestamps is forecast, a shorter one
repeats its last gap, a single one waits 3,600 s.

``arima_dtype`` selects the bank's precision, as in :mod:`.hpm`.
"""
from __future__ import annotations

import collections
from typing import Sequence

import numpy as np

from .arima import ARIMA, predict_next_timestamp
from .fpgrowth import RulePredictor
from .hpm import PrefetchOp

PREFETCH_OFFSET = 0.8


class MeshRulePredictor:
    """MD2: regional-mesh association rules + ARIMA timing, for all users."""

    def __init__(self, n_locs: int, mesh_locs: int = 5, min_support: int = 10,
                 min_confidence: float = 0.4, history: int = 60,
                 arima_dtype: str = "float32"):
        self.n_locs = n_locs
        self.mesh_locs = mesh_locs          # locations per mesh cell
        self.min_support = min_support
        self.min_confidence = min_confidence
        self.history = history
        self.arima = ARIMA(n=history, dtype=arima_dtype)
        self._user_ts: dict[int, list[float]] = collections.defaultdict(list)
        self._user_recent_cells: dict[int, list[int]] = \
            collections.defaultdict(list)
        self._cell_objs: dict[int, collections.Counter] = \
            collections.defaultdict(collections.Counter)
        self.rule_predictor: RulePredictor | None = None

    def _cell(self, obj: int) -> int:
        return (obj % self.n_locs) // self.mesh_locs

    def fit(self, requests: Sequence) -> "MeshRulePredictor":
        sessions: dict[tuple[int, int], list[int]] = \
            collections.defaultdict(list)
        for r in requests:
            # session = (user, hour bucket): cells co-accessed close in time
            sessions[(r.user_id, int(r.ts // 3600))].append(self._cell(r.obj))
            self._cell_objs[self._cell(r.obj)][r.obj] += 1
        txs = [list(dict.fromkeys(v)) for v in sessions.values()]
        self.rule_predictor = RulePredictor(txs, self.min_support,
                                            self.min_confidence)
        return self

    def observe(self, r) -> None:
        ts_list = self._user_ts[r.user_id]
        # distinct timestamps: a multi-stream user asks several at once
        if not ts_list or r.ts > ts_list[-1]:
            ts_list.append(r.ts)
        if len(ts_list) > self.history + 1:
            del ts_list[0]
        cells = self._user_recent_cells[r.user_id]
        cells.append(self._cell(r.obj))
        if len(cells) > 8:
            del cells[0]
        self._cell_objs[self._cell(r.obj)][r.obj] += 1

    def predict(self, r, top_n: int = 3
                ) -> list[tuple[int, float, float, float]]:
        """Prefetch plan ``[(obj, prefetch_ts, tr_start, tr_end)]``."""
        ts_hist = np.array(self._user_ts.get(r.user_id, [r.ts]))
        if ts_hist.size >= 4:
            next_ts = predict_next_timestamp(ts_hist, self.arima)
        else:
            next_ts = r.ts + (ts_hist[-1] - ts_hist[-2] if ts_hist.size >= 2
                              else 3600.0)
        width = r.tr_end - r.tr_start
        cells: list[int] = []
        if self.rule_predictor is not None:
            cells = list(self.rule_predictor.predict(
                self._user_recent_cells.get(r.user_id, [self._cell(r.obj)]),
                top_n=top_n))
        candidate_objs: list[int] = [r.obj]
        for c in cells:
            pops = self._cell_objs.get(c)
            if pops:
                candidate_objs.extend(o for o, _ in pops.most_common(2))
        plan: list[tuple[int, float, float, float]] = []
        seen = set()
        for obj in candidate_objs:
            if obj in seen:
                continue
            seen.add(obj)
            # the window advanced to the predicted access time
            plan.append((obj, float(next_ts), float(next_ts - width),
                         float(next_ts)))
            if len(plan) >= top_n:
                break
        return plan


class MD2Adapter:
    """Predict, then observe; every planned fetch is a scheduled prefetch."""

    name = "md2"

    def __init__(self, n_locs: int, training_requests: Sequence | None = None,
                 top_n: int = 3, arima_dtype: str = "float32"):
        self.model = MeshRulePredictor(n_locs, arima_dtype=arima_dtype)
        if training_requests:
            self.model.fit(training_requests)
        self.top_n = top_n

    def observe(self, r) -> list[PrefetchOp]:
        plan = self.model.predict(r, self.top_n)
        self.model.observe(r)
        return [PrefetchOp(r.ts + PREFETCH_OFFSET * max(0.0, ts - r.ts),
                           r.user_id, obj, s, e, "mining")
                for obj, ts, s, e in plan]
