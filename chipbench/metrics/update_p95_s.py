"""95th percentile, by nearest rank, of the latency of every update in
the window."""
import math


def read(run):
    lat = sorted(t1 - t0 for t0, t1, _ in run.window.samples)
    return lat[math.ceil(0.95 * len(lat)) - 1]
