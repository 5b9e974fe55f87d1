"""Grouped-kernel launches of each search (``SearchStats.kernel_launches``),
mean over the window's answered searches."""
import numpy as np


def read(run):
    n = [r.stats.kernel_launches for r in run.answered("search") if r.stats is not None]
    return float(np.mean(n)) if n else None
