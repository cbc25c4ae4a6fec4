"""Window preparation (request arrays, chunk ranges and outcome columns,
the scaled requests handed to the planner) and the fold of each window's
outcomes, from the program's ``vdc.engine.prep`` and ``vdc.engine.fold``
spans, as a share of the window."""
from vdcbench import program


def read(ctx):
    return program.share(
        ctx, program.seconds(("vdc.engine.prep", "vdc.engine.fold")))
