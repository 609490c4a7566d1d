"""95th percentile (nearest rank) of issue-to-ready wall time, over
every call of the window."""
import math


def read(run):
    lat = sorted((ready - issue) * 1e3 for issue, _, ready in run.calls)
    if not lat:
        return None
    return lat[math.ceil(0.95 * len(lat)) - 1]
