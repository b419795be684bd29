"""live_lane_share: lanes that took a hop over the lane slots of every
flush the window served, as a percentage (``TopologyReport.counters``)."""


def read(ctx):
    c = getattr(ctx, "counters", None) or {}
    if not c.get("lane_slots"):
        return None
    return 100.0 * c["live_lanes"] / c["lane_slots"]
