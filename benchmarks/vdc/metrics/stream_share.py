"""The streaming engine inside the event loop (``absorb``, ``subscribe``,
``pushes_until``), from the program's ``stream_ns`` accumulator, as a share
of the window."""
from vdcbench import program


def read(ctx):
    secs = program.meta_seconds(program.LOOPS, "stream_ns")
    return program.share(ctx, secs)
