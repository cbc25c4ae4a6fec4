"""Serving requests in the dynamic event loop (``_serve_event``, array and
scalar paths), from the program's ``serve_ns`` loop accumulator, as a share
of the window."""
from vdcbench import program


def read(ctx):
    secs = program.meta_seconds(program.LOOPS, "serve_ns")
    return program.share(ctx, secs)
