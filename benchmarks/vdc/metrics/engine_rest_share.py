"""The replay outside the wrapped layers (window prep, the dynamic event
loop, serving, prefetch and push application): 100 minus the share the
layer spans cover."""
from vdcbench import layers


def read(ctx):
    if not ctx.spans:
        return None
    return layers.share(ctx, ctx.window_s - layers.covered(ctx))
