"""Process start to the window's opening: imports and device start-up,
trace synthesis, compile-cache loads (compiles on a checkout's first run),
warm-up of every bank bucket and the Lloyd shape, one untimed window."""


def read(ctx):
    return ctx.setup_s
