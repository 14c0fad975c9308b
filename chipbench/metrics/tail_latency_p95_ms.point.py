"""95th percentile of the latency of every point read of the window, from
when it was due to when its bytes were on the host, in ms (a traced run:
the profiler runs through the window's last seconds)."""
import numpy as np


def read(r):
    lat = r.latencies_ms
    return float(np.percentile(lat, 95)) if lat is not None and lat.size \
        else None
