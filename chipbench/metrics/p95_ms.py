"""95th percentile latency, due to answer on the host, of every request of
the window."""
import numpy as np


def read(run):
    lat = [r.latency for r in run.answered]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
