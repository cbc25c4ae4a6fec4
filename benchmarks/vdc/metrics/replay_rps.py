"""Requests handed to replay jobs in the window (those of a job that raised
excluded), over the window's wall time: from its opening until the last
job returned."""


def read(ctx):
    return ctx.requests / ctx.window_s if ctx.window_s > 0 else None
