"""A rehearsed run of one cell with one fault planted in the program under
the timed path; the rest of the run (set-up, window, check) is the
harness's own.

    python fault_run.py <fault> <size> [--strategy <s>] [--md2-bank] \
        --workload <cell> --seed <n> --seconds <s>

``size`` is ``tiny`` (the harness's ``--rehearse`` sizes) or ``cell`` (the
cell's own sizes and check, for faults that the tiny size is too small to
show; give ``--seconds`` room for a job to reach the checked prefix).
``--strategy`` runs the cell's spec under another strategy (for instance
``cache_only``, which the engine serves through its static block replay,
``_run_static``, or ``md2``, which predicts online inside the event loop),
with the rest of the spec as the cell has it.  ``--md2-bank`` gives
``md2``'s model the fixed-width ARIMA bank (``ARIMA(n=60, bank=True)``) in
place of its single-series program (``bank=False``), the semantics that the
reference has and that a window planner for ``md2`` needs.

Faults: ``none``; ``state_unchanged`` (the serving step leaves every
cache as it was: inserts and block commits are dropped); ``half_batch``
(each ARIMA bank call answers the first half of its real rows and gives
the other real rows their mean; padding rows are left alone);
``answer_altered`` (one request's local bytes are off by one where the
engine writes them); ``static_answer_altered`` (the eighth request of
each static window has its local bytes off by one once the window is
served); ``static_counter_altered`` (from its first static window on, the
engine reports one eviction more on its lowest DTN than it made; the
outcomes are untouched); ``md2_op_dropped`` (the eighth ``observe`` call
of each ``md2`` job loses its last op where the adapter produces it).  The
cell runs on one chip, so the fault of a left-out exchange between chips
does not arise.
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
    elif fault == "md2_op_dropped":
        from repro.core.delivery import MD2Adapter

        observe = MD2Adapter.observe

        def dropping(self, r):
            ops = observe(self, r)
            self._observed = getattr(self, "_observed", 0) + 1
            return ops[:-1] if self._observed == 8 else ops

        MD2Adapter.observe = dropping
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


def force_md2_bank() -> None:
    """Give ``md2``'s model the fixed-width ARIMA bank."""
    from repro.core import mining
    from repro.core.arima import ARIMA

    init = mining.MeshRulePredictor.__init__

    def banked(self, *args, **kw):
        init(self, *args, **kw)
        self.arima = ARIMA(n=self.history, bank=True)

    mining.MeshRulePredictor.__init__ = banked


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


if __name__ == "__main__":
    argv = sys.argv[3:]
    strategy = None
    if argv[:1] == ["--strategy"]:
        strategy, argv = argv[1], argv[2:]
    if argv[:1] == ["--md2-bank"]:
        argv = argv[1:]
        force_md2_bank()
    plant(sys.argv[1])
    from vdcbench import harness

    set_size(harness, sys.argv[2], strategy)
    sys.exit(harness.main(argv + ["--rehearse"], time.perf_counter()))
