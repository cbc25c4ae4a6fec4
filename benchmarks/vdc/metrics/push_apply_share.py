"""Applying stream pushes (``_apply_push``) in the event loop and the final
drain, from the program's ``push_ns`` accumulators, as a share of the
window."""
from vdcbench import program


def read(ctx):
    secs = program.meta_seconds(program.LOOPS, "push_ns")
    return program.share(ctx, secs)
