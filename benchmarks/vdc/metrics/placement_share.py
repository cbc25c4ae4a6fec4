"""Placement (``VectorVDCSimulator._run_placement``: k-means Lloyd on the
device, hub choice and replication) as a share of the window."""
from vdcbench import layers


def read(ctx):
    s = layers.seconds(ctx, "placement")
    return layers.share(ctx, s) if s > 0 else None
