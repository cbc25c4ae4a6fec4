"""Wrappers the benchmark installs around the program's layer entry points.

Each wrapper replaces a module or class attribute of the program with a
function that calls the original; the program's code is unchanged.  They
serve three purposes:

- counting ARIMA bank program calls (as ``BankCalls`` in ``chip_smoke.py``
  at commit bcb7c9a did) — always on;
- layer spans, only in a traced run: host-clock intervals around
  ``delivery.make_prefetcher`` (training), ``BatchedHPMPlanner.plan_window``
  (planning), each bank program call ended by ``block_until_ready`` on its
  output (device), and ``VectorVDCSimulator._run_placement`` (placement).
  Each span is also written into the profiler's trace as a
  ``jax.profiler.TraceAnnotation`` named ``vdc.<layer>``, so that idle time
  on the device can be blamed on the host work running during it;
- capturing what the timed path produced for the correctness check, in the
  one job the harness arms: the per-request outcome columns, the engine's
  integer counters at the end of each of its stream windows, whichever
  path served the window (the dynamic event loop,
  ``VectorVDCSimulator._run_dyn_window``, or the static block replay,
  ``VectorVDCSimulator._run_static``), and each request's prefetch ops,
  however they were produced:

  - by a window planner: the per-request op lists that the planner hands
    to ``delivery._route_planned_ops``, the routing that turns them into
    scheduled prefetches and stream subscriptions.  A planner is checked
    only if it routes its windows through that function;
  - online, where ``_run_dyn_window`` runs with no planner: the list that
    the simulator's prefetcher returns from each ``observe`` call, in
    request order.  An adapter that turns some ops into subscriptions
    inside ``observe`` (``hpm`` without batched prediction, which no cell
    runs) returns only the rest, so its stream hand-offs are not seen.
"""
from __future__ import annotations

import time

import jax
import numpy as np

OUTCOME_COLUMNS = ("bytes", "latency", "transfer", "local", "prefetched",
                   "peer", "origin", "peer_time")


class Probes:
    def __init__(self, spans: bool):
        self.spans_on = spans
        self.spans: list[tuple[str, float, float]] = []
        self.bank_calls = 0
        self._capturing = False
        self._cols: list[tuple] = []
        self._ops: list = []
        self._counters: dict[int, dict] = {}
        self._seen = 0

    def capture(self, on: bool) -> None:
        """Start (clearing what was kept) or stop keeping every window's
        outcome columns, prefetch ops and closing counters."""
        if on:
            self._cols, self._ops, self._counters = [], [], {}
            self._seen = 0
        self._capturing = on

    def counters_after(self, n_requests: int) -> dict | None:
        """The counters as they stood at the end of the stream window that
        closed after ``n_requests`` requests, if one did."""
        return self._counters.get(n_requests)

    @property
    def columns(self) -> dict[str, np.ndarray] | None:
        if not self._cols:
            return None
        return {name: np.concatenate([w[i] for w in self._cols])
                for i, name in enumerate(OUTCOME_COLUMNS)}

    @property
    def ops(self) -> list | None:
        return self._ops or None

    def _window_served(self, sim, A: dict, stream_engine) -> None:
        """Keep the engine's counters as they stand once a stream window is
        served, keyed by the requests served so far."""
        if self._capturing:
            self._seen += len(A["arr"])
            self._counters[self._seen] = _counters_of(sim, stream_engine)

    def _span(self, layer: str, fn, *args, **kw):
        with jax.profiler.TraceAnnotation("vdc." + layer):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kw)
            finally:
                self.spans.append((layer, t0, time.perf_counter()))

    def install(self) -> None:
        from repro.core import arima, delivery, hpm, simulator
        from repro.core.engine import VectorVDCSimulator

        probes = self
        compiled_bank = arima._compiled_bank

        def counted_bank(*key):
            program = compiled_bank(*key)

            def call(rows):
                probes.bank_calls += 1
                if not probes.spans_on:
                    return program(rows)
                return probes._span("bank", _synced, program, rows)

            return call

        arima._compiled_bank = counted_bank

        route_planned_ops = delivery._route_planned_ops

        def routed(requests, per_req):
            if probes._capturing:
                probes._ops.extend(per_req)
            return route_planned_ops(requests, per_req)

        delivery._route_planned_ops = routed

        add_columns = simulator.OutcomeAggregate.add_columns

        def folded(agg, *cols):
            if probes._capturing:
                probes._cols.append(tuple(np.array(c, copy=True)
                                          for c in cols))
            return add_columns(agg, *cols)

        simulator.OutcomeAggregate.add_columns = folded

        run_dyn_window = VectorVDCSimulator._run_dyn_window

        def windowed(sim, A, stream_engine, heap, counter, planner):
            if planner is not None or not probes._capturing:
                run_dyn_window(sim, A, stream_engine, heap, counter, planner)
            else:
                pf = sim.pf
                observe = pf.observe

                def observed(r):
                    ops = observe(r)
                    probes._ops.append(ops)
                    return ops

                pf.observe = observed
                try:
                    run_dyn_window(sim, A, stream_engine, heap, counter,
                                   planner)
                finally:
                    del pf.observe
            probes._window_served(sim, A, stream_engine)

        VectorVDCSimulator._run_dyn_window = windowed

        run_static = VectorVDCSimulator._run_static

        def static_windowed(sim, A):
            run_static(sim, A)
            probes._window_served(sim, A, None)

        VectorVDCSimulator._run_static = static_windowed

        if not self.spans_on:
            return
        make_prefetcher = delivery.make_prefetcher

        def trained(*args, **kw):
            return probes._span("train", make_prefetcher, *args, **kw)

        delivery.make_prefetcher = trained

        plan_window = hpm.BatchedHPMPlanner.plan_window

        def planned(planner, requests):
            return probes._span("plan", plan_window, planner, requests)

        hpm.BatchedHPMPlanner.plan_window = planned

        run_placement = VectorVDCSimulator._run_placement

        def placed(sim, now):
            return probes._span("placement", run_placement, sim, now)

        VectorVDCSimulator._run_placement = placed


def _counters_of(sim, stream_engine) -> dict:
    """The engine's counters in the reference's form
    (``ref.simulator.counters_of``)."""
    out = {}
    for d, c in sorted(sim.caches.items()):
        s = c.to_cache_stats()
        out[f"dtn{d}"] = (s.hits, s.misses, s.hit_bytes, s.miss_bytes,
                          s.evictions, s.inserted_bytes)
    out["stream_pushes"] = (stream_engine.pushes_emitted
                            if stream_engine is not None else 0,)
    return out


def _synced(program, rows):
    out = program(rows)
    out.block_until_ready()
    return out
