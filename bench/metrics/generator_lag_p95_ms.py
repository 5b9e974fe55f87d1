"""95th percentile of how late the client sent each request after it was
due, in milliseconds: a starved generator is not a fast server."""
import numpy as np


def read(run):
    lag = [r.sent - r.due for r in run.loop.requests if r.sent is not None]
    return float(np.percentile(lag, 95)) * 1e3 if lag else None
