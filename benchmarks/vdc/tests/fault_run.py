"""A rehearsed run of one cell with one fault planted in the program under
the timed path; the rest of the run (set-up, window, check) is the
harness's own.

    python fault_run.py <fault> <size> --workload <cell> --seed <n> \
        --seconds <s>

``size`` is ``tiny`` (the harness's ``--rehearse`` sizes) or ``cell`` (the
cell's own sizes and check, for faults that the tiny size is too small to
show; give ``--seconds`` room for a job to reach the checked prefix).

Faults: ``none``; ``state_unchanged`` (the serving step leaves every
cache as it was: inserts are dropped); ``half_batch`` (each ARIMA bank
call answers the first half of its real rows and gives the other real rows
their mean; padding rows are left alone);
``answer_altered`` (one request's local bytes are off by one where the
engine writes them).  The cell runs on one chip, so the fault of a
left-out exchange between chips does not arise.
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
    elif fault != "none":
        raise SystemExit(f"unknown fault {fault!r}")


if __name__ == "__main__":
    plant(sys.argv[1])
    from vdcbench import harness

    if sys.argv[2] == "cell":
        harness.rehearse_spec = lambda spec: spec
    elif sys.argv[2] != "tiny":
        raise SystemExit(f"unknown size {sys.argv[2]!r}")
    sys.exit(harness.main(sys.argv[3:] + ["--rehearse"], time.perf_counter()))
