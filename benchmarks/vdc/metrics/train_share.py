"""Prefetcher training (``delivery.make_prefetcher``: FP-Growth over the
training split) as a share of the window."""
from vdcbench import layers


def read(ctx):
    s = layers.seconds(ctx, "train")
    return layers.share(ctx, s) if s > 0 else None
