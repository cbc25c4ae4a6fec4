"""The event loop's own work (heap pops and pushes, request bookkeeping):
the program's ``vdc.engine.loop`` spans minus their timed calls and their
placement children, as a share of the window."""
from vdcbench import program


def read(ctx):
    return program.share(ctx, program.loop_self_seconds())
