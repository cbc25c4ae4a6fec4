"""Program-request fast-path decisions of the batched HPM planner that fell
back to the exact ``_gap_stats`` (inside the running sums' rounding
margin), as a percent of the decisions made, from the program's counters
``gap_exact`` and ``gap_decisions`` on its ``vdc.hpm.plan`` spans."""
from vdcbench import program


def read(ctx):
    plans = program.spans(("vdc.hpm.plan",))
    if plans is None or not any("gap_decisions" in r.meta for r in plans):
        return None
    made = sum(r.meta.get("gap_decisions", 0) for r in plans)
    exact = sum(r.meta.get("gap_exact", 0) for r in plans)
    return 100.0 * exact / made if made else None
