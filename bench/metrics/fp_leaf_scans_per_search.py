"""Leaves each search scanned at full precision from the node cache,
bypassing the grouped kernel (``SearchStats.fp_leaf_scans``), mean over
the window's answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "fp_leaf_scans")
