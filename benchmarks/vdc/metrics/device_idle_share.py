"""Share of the window in which the chip ran no program, from the profiler
trace: 1 minus the union of the device's program executions."""
from vdcbench import devtrace


def read(ctx):
    tr = ctx.trace
    if tr is None or tr["window"] is None or ctx.plane not in tr["device"]:
        return None
    lo, hi = tr["window"]
    return 100.0 * (1.0 - devtrace.busy_ns(tr, ctx.plane) / (hi - lo))
