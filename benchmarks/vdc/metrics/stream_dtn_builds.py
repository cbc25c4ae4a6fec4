"""Sorted DTN tuples the streaming engine built for its subscriptions in
the window (a count, one per ``subscribe`` or ``unsubscribe``), from the
program's ``dtn_builds`` counter on its ``vdc.engine.loop`` spans."""
from vdcbench import program


def read(ctx):
    loops = program.spans(("vdc.engine.loop",))
    if loops is None or not any("dtn_builds" in r.meta for r in loops):
        return None
    return float(sum(r.meta.get("dtn_builds", 0) for r in loops))
