"""Applying prefetch ops (``_apply_prefetch``) in the event loop and the
final drain, from the program's ``prefetch_ns`` accumulators, as a share of
the window."""
from vdcbench import program


def read(ctx):
    secs = program.meta_seconds(program.LOOPS, "prefetch_ns")
    return program.share(ctx, secs)
