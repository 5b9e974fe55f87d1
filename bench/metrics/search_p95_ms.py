"""95th percentile of every search's time from when it was due to its
page, in milliseconds (open loop: the wait a stall puts on later
requests counts)."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.answered("search")]
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
