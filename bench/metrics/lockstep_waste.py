"""lockstep_waste: lanes x the slowest lane's hops over the hops the lanes
took, over full 64-query flushes of pool queries (``SearchStats.hops``
from ``PIMCQGEngine.search(pad_to=64)`` after the window). Dead lanes read
0 hops and still run. A count: it repeats exactly for one seed."""

from bench.metrics_lib import lockstep_waste


def read(ctx):
    return None if ctx.hops is None else lockstep_waste(ctx.hops)
