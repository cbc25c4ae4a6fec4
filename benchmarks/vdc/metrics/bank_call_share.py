"""ARIMA bank program calls, each ended by ``block_until_ready`` on its
output, as a share of the window."""
from vdcbench import layers


def read(ctx):
    s = layers.seconds(ctx, "bank")
    return layers.share(ctx, s) if s > 0 else None
