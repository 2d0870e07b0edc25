"""take_p95_ms: the 95th percentile (nearest rank) of the latency of every
take request of the window, in milliseconds (host clock)."""

import math


def read(win):
    lat = sorted(win.latencies_s)
    return 1e3 * lat[max(0, math.ceil(0.95 * len(lat)) - 1)]
