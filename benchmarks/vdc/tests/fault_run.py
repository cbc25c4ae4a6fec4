"""A rehearsed run of one cell with one fault planted in the program under
the timed path; the rest of the run (set-up, window, check) is the
harness's own.

    python fault_run.py <fault> <size> [--strategy <s>] [--md2-bank] \
        [--md2-planned] --workload <cell> --seed <n> --seconds <s>

``size`` is ``tiny`` (the harness's ``--rehearse`` sizes) or ``cell`` (the
cell's own sizes and check, for faults that the tiny size is too small to
show; give ``--seconds`` room for a job to reach the checked prefix).
``--strategy`` runs the cell's spec under another strategy (for instance
``cache_only``, which the engine serves through its static block replay,
``_run_static``, or ``md2``, the paper's mining baseline), with the rest of
the spec as the cell has it.  ``--md2-bank`` gives ``md2``'s model the
fixed-width ARIMA bank (``ARIMA(n=60, bank=True)``), the semantics that the
reference has and that a window planner for ``md2`` needs.
``--md2-planned`` gives ``MD2Adapter`` a window planner, which calls
``observe`` for each request of a window and routes the lists through
``delivery._route_planned_ops``: ``md2``'s ops depend on the request
stream alone, so planning a window ahead gives the online ops.  Each option
changes nothing where the program has what it adds already (a model whose
ARIMA is on the bank; an ``MD2Adapter.planner`` of its own), so a case
asserts the same thing whichever way the program runs ``md2``: online or
planned, with a single-series fit or the bank.

Faults: ``none``; ``state_unchanged`` (the serving step leaves every
cache as it was: inserts and block commits are dropped); ``half_batch``
(each ARIMA bank call answers the first half of its real rows and gives
the other real rows their mean; padding rows are left alone);
``answer_altered`` (one request's local bytes are off by one where the
engine writes them); ``static_answer_altered`` (the eighth request of
each static window has its local bytes off by one once the window is
served); ``static_counter_altered`` (from its first static window on, the
engine reports one eviction more on its lowest DTN than it made; the
outcomes are untouched); ``md2_single_series`` (``md2``'s model gets the
single-series program, ``ARIMA(n=60, bank=False)``, whatever the program
has: its forecasts are not the bank's, so its ops depart from the
reference's); ``md2_op_dropped`` (the eighth request with ops of each
``md2`` job loses its last op, once, where the ops that the check captures
are produced: in ``MD2Adapter.observe`` online, in
``delivery._route_planned_ops`` where a planner routes its window without
``observe``; one request's ops then differ on either route).  The cell
runs on one chip, so the fault of a left-out exchange between chips does
not arise.
"""
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]


def plant(fault: str) -> None:
    if fault == "state_unchanged":
        from repro.core import cache

        cache.IntLRUState.insert_batch = lambda self, keys, size_each: None
        cache.IntLRUState.insert_one = lambda self, k, size: None
        cache.IntLRUState.upsert_seq = lambda self, keys, size_each: None
        cache.IntLRUState.upsert_batch = lambda self, keys, size_each: None
        cache.IntLRUState.commit_unique = lambda self, *args: None
    elif fault == "half_batch":
        import numpy as np

        from repro.core import arima

        compiled_bank = arima._compiled_bank

        def halved(*key):
            program = compiled_bank(*key)

            def call(rows):
                out = program(rows)
                # the program pads a short batch by repeating its first row
                x = np.asarray(rows)
                k = len(x)
                while k > 1 and np.array_equal(x[k - 1], x[0]):
                    k -= 1
                half = (k + 1) // 2
                if k < 2:
                    return out
                return out.at[half:k].set(out[:half].mean())

            return call

        arima._compiled_bank = halved
    elif fault == "answer_altered":
        from repro.core.engine import VectorVDCSimulator

        serve = VectorVDCSimulator._serve_event

        def altered(self, idx, *args):
            serve(self, idx, *args)
            if idx == 7:
                self._o_loc[idx] += 1

        VectorVDCSimulator._serve_event = altered
    elif fault == "static_answer_altered":
        from repro.core.engine import VectorVDCSimulator

        run_static = VectorVDCSimulator._run_static

        def altered(self, A):
            run_static(self, A)
            if len(A["arr"]) > 7:
                self._o_loc[7] += 1

        VectorVDCSimulator._run_static = altered
    elif fault == "static_counter_altered":
        from repro.core.engine import VectorVDCSimulator

        run_static = VectorVDCSimulator._run_static

        def miscounting(self, A):
            run_static(self, A)
            if not getattr(self, "_miscounted", False):
                self._miscounted = True
                self.caches[min(self.caches)].evictions += 1

        VectorVDCSimulator._run_static = miscounting
    elif fault == "md2_single_series":
        set_md2_bank(False)
    elif fault == "md2_op_dropped":
        _drop_md2_op()
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def set_md2_bank(bank: bool) -> None:
    """Give ``md2``'s model ``ARIMA(n=history, bank=bank)`` where its own
    ARIMA has the other setting."""
    from repro.core import mining
    from repro.core.arima import ARIMA

    init = mining.MeshRulePredictor.__init__

    def set_bank(self, *args, **kw):
        init(self, *args, **kw)
        if self.arima.bank != bank:
            self.arima = ARIMA(n=self.history, bank=bank)

    mining.MeshRulePredictor.__init__ = set_bank


def plan_md2() -> None:
    """Give ``MD2Adapter`` a window planner that observes each request of
    the window and routes the lists, unless it has a planner of its own."""
    from repro.core import delivery

    if hasattr(delivery.MD2Adapter, "planner"):
        return

    class ObservingPlanner:
        def __init__(self, adapter):
            self.adapter = adapter

        def plan_window(self, requests):
            return delivery._route_planned_ops(
                requests, [self.adapter.observe(r) for r in requests])

    delivery.MD2Adapter.planner = lambda adapter: ObservingPlanner(adapter)


# md2_op_dropped: which request with ops, counted from each job's start
DROPPED_AT = 8


def _drop_md2_op() -> None:
    """Plant ``md2_op_dropped`` at both seams where ``md2``'s ops can be
    produced for the check.  Each seam counts the requests with ops that
    pass it; the first to reach the eighth drops that request's last op,
    and the job's one drop is spent.  A planner that calls ``observe`` and
    then routes the window meets the ``observe`` seam first."""
    from repro.core import delivery
    from vdcbench import probes

    job = {"observe": 0, "route": 0, "spent": True}

    def count(seam: str) -> bool:
        """Whether this request with ops is the one to lose an op."""
        if job["spent"]:
            return False
        job[seam] += 1
        job["spent"] = job[seam] == DROPPED_AT
        return job["spent"]

    make_prefetcher = delivery.make_prefetcher

    def fresh_job(*args, **kw):
        pf = make_prefetcher(*args, **kw)
        job.update(observe=0, route=0,
                   spent=not isinstance(pf, delivery.MD2Adapter))
        return pf

    delivery.make_prefetcher = fresh_job

    observe = delivery.MD2Adapter.observe

    def dropping(self, r):
        ops = observe(self, r)
        return ops[:-1] if ops and count("observe") else ops

    delivery.MD2Adapter.observe = dropping

    # the probes capture a planner's lists where they enter
    # _route_planned_ops: this seam goes in front of that capture, so that
    # the capture sees the drop as it sees the observe seam's
    install = probes.Probes.install

    def install_then_plant(self):
        install(self)
        route = delivery._route_planned_ops

        def dropping_route(requests, per_req):
            per_req = list(per_req)
            for i, ops in enumerate(per_req):
                if ops and count("route"):
                    per_req[i] = ops[:-1]
            return route(requests, per_req)

        delivery._route_planned_ops = dropping_route

    probes.Probes.install = install_then_plant


def set_size(harness, size: str, strategy: str | None) -> None:
    """Make the harness's ``--rehearse`` run at ``size``, under
    ``strategy`` where one is given."""
    if size == "cell":
        sized = lambda spec: spec  # noqa: E731
    elif size == "tiny":
        sized = harness.rehearse_spec
    else:
        raise SystemExit(f"unknown size {size!r}")
    if strategy is None:
        harness.rehearse_spec = sized
    else:
        harness.rehearse_spec = lambda spec: {**sized(spec),
                                              "strategy": strategy}


def apply_options(argv: list[str]) -> tuple[str | None, list[str]]:
    """Apply the options at the head of ``argv`` (``--strategy <s>``,
    ``--md2-bank``, ``--md2-planned``); ``(strategy, the rest)``."""
    strategy = None
    while argv:
        if argv[0] == "--strategy":
            strategy, argv = argv[1], argv[2:]
        elif argv[0] == "--md2-bank":
            set_md2_bank(True)
            argv = argv[1:]
        elif argv[0] == "--md2-planned":
            plan_md2()
            argv = argv[1:]
        else:
            break
    return strategy, argv


if __name__ == "__main__":
    strategy, argv = apply_options(sys.argv[3:])
    plant(sys.argv[1])
    from vdcbench import harness

    set_size(harness, sys.argv[2], strategy)
    sys.exit(harness.main(argv + ["--rehearse"], time.perf_counter()))
