"""Events of the dynamic event loop and its drain in the window: requests
served plus prefetch ops and stream pushes applied (a count, from the
program's per-loop counters)."""
from vdcbench import program


def read(ctx):
    return program.meta_sum(program.LOOPS,
                            ("requests", "prefetch_events", "push_events"))
