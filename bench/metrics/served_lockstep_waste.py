"""served_lockstep_waste: lane slots x the slowest lane's hops over the
hops the lanes took, summed over every flush the window served
(``TopologyReport.counters``: ``slot_hops`` / ``hops``)."""


def read(ctx):
    c = getattr(ctx, "counters", None) or {}
    if not c.get("hops"):
        return None
    return c["slot_hops"] / c["hops"]
