"""Prediction planning (``BatchedHPMPlanner.plan_window``) minus the bank
calls inside it, as a share of the window."""
from vdcbench import layers


def read(ctx):
    plan = layers.seconds(ctx, "plan")
    if plan <= 0:
        return None
    return layers.share(ctx, plan - layers.seconds_within(ctx, "bank", "plan"))
