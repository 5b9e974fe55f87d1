"""Median of every search's time from when it was due to its page, in
milliseconds (traced run)."""
import numpy as np


def read(run):
    lat = [r.latency_s for r in run.answered("search")]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
