"""Leaves each search opened (``SearchStats.leaves_opened``), mean over
the window's answered searches."""
import numpy as np


def read(run):
    n = [r.stats.leaves_opened for r in run.answered("search") if r.stats is not None]
    return float(np.mean(n)) if n else None
