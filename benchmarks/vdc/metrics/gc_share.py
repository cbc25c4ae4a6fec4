"""Python collector pauses during replay jobs (every generation), from the
``gc_ns`` the program records on its ``vdc.sim.job`` spans, as a share of
the window."""
from vdcbench import program


def read(ctx):
    secs = program.meta_seconds(("vdc.sim.job",), "gc_ns")
    return program.share(ctx, secs)
