"""Milliseconds each search spent in store and node-cache reads
(``SearchStats.fetch_s``, the ``ecp.fetch`` spans), mean over the
window's answered searches."""
from bench.lib.searches import mean


def read(run):
    return mean(run, "fetch_s", 1e3)
