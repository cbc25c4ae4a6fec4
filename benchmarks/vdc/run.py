"""Run one cell of the VDC delivery benchmark once.

    python3 benchmarks/vdc/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1> [--rehearse]

The cell, its configuration and its traffic mix are read from
``BENCHMARK.json`` at the checkout's root and the files it names.  The last
line of standard output is the result as one JSON object; the numbers the
correctness check compared are the last lines of standard error.  With
``--rehearse`` the cell runs end to end on the CPU at a tiny size and
prints no device metric.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from vdcbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
